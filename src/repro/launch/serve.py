"""Serving driver: batched request decoding with top-k selective
attention over a KV cache (continuous-batching-lite: fixed batch slots,
**per-slot positions**, new requests claim finished slots).

Each slot owns its decode position; claiming a slot resets its
per-request state (``models.decode.reset_slot``), so a request never
inherits the previous occupant's KV contents — and requests of
different lengths decode concurrently at their own offsets.  Latency is
reported per request (claim → last token), not just aggregate tok/s.

**Cache layout** (``cfg.kv_cache_layout``):

* ``"contiguous"`` — one (max_len, KV, D) region per slot per layer:
  simple, but every slot reserves worst-case HBM for its whole life.
* ``"paged"`` — a global page pool + per-slot page table
  (``core/paging.py``).  The driver owns the host-side allocator:
  pages map on append (a slot holds only ``ceil(pos/page)`` pages),
  free when its request completes, and pool exhaustion becomes
  *backpressure* — the claim loop defers new requests (admission
  control), and a mid-flight slot that cannot map its next page at a
  page boundary stalls for a step (its token is re-fed once a page
  frees; the overflow page swallows the discarded write).  The run
  report includes page occupancy: HBM reserved vs actually used, and
  the reserved-bytes ratio vs the contiguous layout.

**Prefill→decode handoff** (``prompt_len > 1``, dense/moe): prompts
prefill in one full-sequence pass (``models.decode.prefill_prompt``)
whose K/V rows and *seeded decode plan* install into the claimed slot —
the first decode step starts planned (summaries + the prompt tail's
selected blocks) instead of running a cold full re-plan.

**Shared-prefix page cache** (``cfg.kv_prefix_cache``, paged only): a
claim first walks the prompt-prefix trie (``core.paging.PrefixCache``)
and maps the longest cached prefix's pages straight into the new
slot's table — refcount bump, zero copy, and prefill runs only over
the unmatched tail (its queries attend over the gathered prefix K/V,
so the math is the full prefill's, minus the matched positions'
FLOPs).  Shared pages are immutable: any append that would land in
one (in particular the owner's first append into its registered
partial prompt page) goes through copy-on-write — allocate, copy
device-side, remap — or stalls the step when the pool is dry, and the
driver evicts least-recently-used trie pages under pressure.
Completion/preemption decrement refcounts, never freeing a page the
trie or another slot still holds.  The run report adds hit-rate,
prefill tokens saved, CoW copies, and shared-vs-private occupancy.

With ``cfg.sata_decode`` routing on, every step fetches only the
planned KV blocks (``core/decode_plan.py`` + the decode gather kernel)
and the driver accumulates both kernel-side and *plan-side* traffic
(full re-plans stream all cached K; the plan state's ``replans``
counter makes the split exact even under ``sata_decode_replan="auto"``).

**Fault tolerance** (paged): preemption prefers **host-swap** over
requeue on the dense/moe families — the victim's private pages (K/V
rows + per-page summary rows), page-table row, position, and complete
decode-plan state move to host numpy (``PageAllocator.swap_out`` +
``models.decode.gather_phys_pages`` / ``capture_plan_state``); shared
trie pages stay resident under their refcounts.  Re-admission scatters
the payload back into fresh pages and reinstalls the plan reset-free,
so decode resumes at the exact position — **zero re-prefill, zero cold
re-plans, bitwise equal to a never-preempted run** (the plan indexes
*logical* blocks and carries its beat phase, so physical page identity
never enters the math).  ``host_swap_bytes`` bounds the host-side
budget (``0`` disables swap; a dry budget falls back to today's
requeue-and-regenerate).  A ``FaultPlan`` (``launch/faults.py``)
passed as ``serve(faults=...)`` drives every backpressure branch
deterministically: pool squeezes/restores, forced preemptions,
admission deferrals, and a mid-serve ``crash_step`` that swaps ALL
live state to host, drops the device cache + allocator, and restores
every in-flight request from its swap handle.
``max_steps_per_request`` retires runaway slots gracefully as
``timed_out``; a request preempted ``preempt_retry_limit`` times
re-admits under a reserved-page guarantee (and is excluded from victim
selection), so repeated-victim livelock is impossible.
``audit_pages`` (default on) runs ``PageAllocator.check_invariants``
after every allocator mutation.

Usage (reduced arch; ``--no-smoke`` serves the published widths):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
      --requests 8 --gen-len 16
"""
from __future__ import annotations

import argparse
import dataclasses
import pickle
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.archs import ARCHS, SMOKE
from repro.core.paging import (PageAllocator, PageIntegrityError,
                               PrefixCache, SharedPrefixIndex)
from repro.launch.compile_cache import use_compile_cache
from repro.launch.faults import FaultPlan
from repro.launch.mesh import make_local_mesh
from repro.launch.spans import Spans
from repro.models import attention as attn
from repro.models import decode as dec
from repro.models import model as mdl


class ServeKilled(RuntimeError):
    """Raised by ``serve(kill_at_step=N)`` — a deterministic stand-in
    for a process crash, injected AFTER the checkpoint block so a
    resumed run replays from the last saved state."""


class QoSController:
    """SLO degradation ladder: deterministic per-slot rung counters
    mapping overload pressure to decode-plan quality knobs.

    Rungs apply cumulatively::

        0  full quality        budget=P, interval=iv, fp32, exact
        1  half plan budget    budget = max(1, P // 2)
        2  slow re-plan beat   interval = iv * 4
        3  int8 rank bounds    quantized (conservative) block ranking
        4  sketch re-plans     hierarchical candidate pre-filter

    ``press(active, severity)`` steps every active slot DOWN
    ``severity`` rungs — within a pressure episode quality is monotone
    non-increasing.  ``tick(active, pressure)`` is the hysteresis
    clock: a pressure-free tick increments a per-slot clear counter,
    and only after ``clear_steps`` consecutive clear ticks does a slot
    recover ONE rung (the counter then resets, so two recoveries are
    always >= ``clear_steps`` apart — no flapping); any pressure
    zeroes every counter.  ``reset(i)`` (new admission) returns the
    slot to full quality immediately: a rung is a property of the
    slot's CURRENT occupant's episode, not of the hardware."""

    MAX_RUNG = 4

    def __init__(self, n_slots: int, p0: int, iv0: int,
                 clear_steps: int = 4):
        self.n_slots = int(n_slots)
        self.p0 = max(1, int(p0))
        self.iv0 = max(1, int(iv0))
        self.clear_steps = max(1, int(clear_steps))
        self.rung = [0] * self.n_slots
        self.clear = [0] * self.n_slots
        self.rung_downs = 0
        self.rung_ups = 0

    def knobs(self, i: int) -> Tuple[int, int, bool, bool]:
        """(budget, interval, quant, sketch) for slot ``i``'s rung."""
        r = self.rung[i]
        return (self.p0 if r < 1 else max(1, self.p0 // 2),
                self.iv0 if r < 2 else self.iv0 * 4,
                r >= 3, r >= 4)

    def vectors(self):
        """Per-slot knob vectors for ``models.decode.set_qos_knobs``."""
        ks = [self.knobs(i) for i in range(self.n_slots)]
        return (np.asarray([k[0] for k in ks], np.int32),
                np.asarray([k[1] for k in ks], np.int32),
                np.asarray([k[2] for k in ks], bool),
                np.asarray([k[3] for k in ks], bool))

    def press(self, active: List[int], severity: int = 1) -> List[int]:
        """Overload signal: degrade every active slot ``severity``
        rungs (clamped at the bottom).  Returns the changed slots."""
        changed = []
        for i in active:
            new = min(self.rung[i] + max(1, int(severity)), self.MAX_RUNG)
            if new != self.rung[i]:
                self.rung[i] = new
                self.rung_downs += 1
                changed.append(i)
            self.clear[i] = 0
        return changed

    def tick(self, active: List[int], pressure: bool) -> List[int]:
        """Per-step hysteresis clock (call once per loop step, after
        this step's pressure is known).  Returns slots that recovered
        one rung."""
        if pressure:
            self.clear = [0] * self.n_slots
            return []
        changed = []
        for i in active:
            if self.rung[i] == 0:
                continue
            self.clear[i] += 1
            if self.clear[i] >= self.clear_steps:
                self.rung[i] -= 1
                self.clear[i] = 0
                self.rung_ups += 1
                changed.append(i)
        return changed

    def reset(self, i: int) -> bool:
        """New admission into slot ``i`` starts at full quality."""
        changed = self.rung[i] != 0
        self.rung[i] = 0
        self.clear[i] = 0
        return changed


def _plan_field(cache: Dict, field: str) -> Optional[jax.Array]:
    """One field of the SATA decode-plan state, on the device, if
    routing is on (hybrid keeps its attention cache under
    ``shared_kv``)."""
    for name in ("kv", "shared_kv"):
        kvc = cache.get(name)
        if isinstance(kvc, dict) and "plan" in kvc:
            return kvc["plan"][field]
    return None


def _plan_counts(cnt: Optional[np.ndarray]) -> Optional[np.ndarray]:
    """Layer-stacked (L, B, KV) plan occupancy from the host copy of
    the plan's ``kv_counts``."""
    return None if cnt is None else cnt.reshape(-1, *cnt.shape[-2:])


def _plan_replans(r: Optional[Union[jax.Array, np.ndarray]]
                  ) -> Optional[np.ndarray]:
    """Cumulative per-(layer, slot) full-re-plan counters from the
    plan's ``replans``, layer axes flattened to (L, B) — both the
    churn-adaptive trigger and the per-slot beat fire independently, so
    the caller attributes deltas to the slots that actually hold live
    requests."""
    return None if r is None else \
        np.asarray(r, np.float64).reshape(-1, r.shape[-1])


def _pick_victim(stalled: List[int], slots: List[Optional[int]],
                 outputs: Dict[int, List[int]], admit_seq: Dict[int, int],
                 protected=()) -> int:
    """Preemption victim policy: the stalled slot with the least
    decoded progress loses the least salvageable work; ties break by
    admission order — the YOUNGEST admission goes first (explicit,
    where ``min`` over insertion order used to decide silently).
    Slots holding protected requests (at the preemption retry limit)
    are skipped unless every candidate is protected."""
    cands = [i for i in stalled if slots[i] not in protected]
    if not cands:
        cands = list(stalled)
    return min(cands, key=lambda i: (len(outputs[slots[i]]),
                                     -admit_seq[slots[i]]))


@dataclasses.dataclass(frozen=True)
class ServeOptions:
    """Workload shape for one :func:`serve` run."""
    n_requests: int = 8
    batch_slots: int = 4
    gen_len: int = 16
    max_len: int = 64
    prompt_len: int = 1
    shared_prefix_len: int = 0        # prompts share their first N
                                      # tokens (a common system prompt)
                                      # — the workload the prefix cache
                                      # exists for


@dataclasses.dataclass(frozen=True)
class ResilienceOptions:
    """Fault-tolerance / overload knobs for :func:`serve` (see the
    module docstring for the failure model each one drives)."""
    host_swap_bytes: Optional[int] = None   # host-swap payload budget
                                            # (None unbounded, 0 =
                                            # requeue-only)
    max_steps_per_request: Optional[int] = None  # deadline watchdog
    preempt_retry_limit: int = 3            # reserved-page guarantee
                                            # past this many preemptions
    audit_pages: Union[bool, str] = True    # allocator invariant audit
                                            # (True | False | "light")
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0
    resume: bool = False
    kill_at_step: Optional[int] = None      # deterministic crash after
                                            # the checkpoint block


_LEGACY_SERVE_KW = {
    # legacy flat kwarg -> (options group, field)
    "n_requests": ("options", "n_requests"),
    "batch_slots": ("options", "batch_slots"),
    "gen_len": ("options", "gen_len"),
    "max_len": ("options", "max_len"),
    "prompt_len": ("options", "prompt_len"),
    "shared_prefix_len": ("options", "shared_prefix_len"),
    "host_swap_bytes": ("resilience", "host_swap_bytes"),
    "max_steps_per_request": ("resilience", "max_steps_per_request"),
    "preempt_retry_limit": ("resilience", "preempt_retry_limit"),
    "audit_pages": ("resilience", "audit_pages"),
    "checkpoint_dir": ("resilience", "checkpoint_dir"),
    "checkpoint_every": ("resilience", "checkpoint_every"),
    "resume": ("resilience", "resume"),
    "kill_at_step": ("resilience", "kill_at_step"),
}

_warned_serve_legacy = False


def _fold_serve_legacy(options: Optional[ServeOptions],
                       resilience: Optional[ResilienceOptions],
                       legacy: Dict[str, Any]
                       ) -> Tuple[ServeOptions, ResilienceOptions]:
    """Map legacy flat ``serve()`` kwargs onto the options dataclasses
    (explicit flat values override group values).  One
    DeprecationWarning per process, naming every legacy kwarg seen."""
    opt = options or ServeOptions()
    res = resilience or ResilienceOptions()
    if legacy:
        unknown = [k for k in legacy if k not in _LEGACY_SERVE_KW]
        if unknown:
            raise TypeError(f"serve() got unexpected keyword argument(s) "
                            f"{unknown}")
        global _warned_serve_legacy
        if not _warned_serve_legacy:
            _warned_serve_legacy = True
            warnings.warn(
                f"flat serve() kwargs {sorted(legacy)} are deprecated; "
                f"pass serve(options=ServeOptions(...), "
                f"resilience=ResilienceOptions(...))",
                DeprecationWarning, stacklevel=3)
        by_group: Dict[str, Dict[str, Any]] = {"options": {},
                                               "resilience": {}}
        for k, v in legacy.items():
            group, field = _LEGACY_SERVE_KW[k]
            by_group[group][field] = v
        if by_group["options"]:
            opt = dataclasses.replace(opt, **by_group["options"])
        if by_group["resilience"]:
            res = dataclasses.replace(res, **by_group["resilience"])
    return opt, res


def serve(arch: str, smoke: bool = True, *,
          seed: int = 0, mesh=None, params=None, cfg=None,
          options: Optional[ServeOptions] = None,
          faults: Optional[FaultPlan] = None,
          resilience: Optional[ResilienceOptions] = None,
          prefix_index: Optional[SharedPrefixIndex] = None,
          replica_id: int = 0,
          **legacy) -> Dict[str, Any]:
    """Serve ``options.n_requests`` requests through ``batch_slots``
    decode slots.  The workload shape lives in :class:`ServeOptions`,
    fault injection in ``faults`` (a :class:`FaultPlan`), and the
    recovery/watchdog knobs in :class:`ResilienceOptions`; the legacy
    flat kwargs (``n_requests=...``, ``checkpoint_dir=...``) still work
    through a deprecation shim.

    Fault-tolerance knobs (see the module docstring): ``faults`` is a
    deterministic ``FaultPlan`` keyed on the loop-step counter;
    ``host_swap_bytes`` caps host-swap payload bytes held at once
    (``None`` = unbounded, ``0`` = requeue-only); a request is retired
    as ``timed_out`` after holding a slot ``max_steps_per_request``
    steps; ``preempt_retry_limit`` preemptions of one request trigger
    the reserved-page re-admission guarantee; ``audit_pages`` keeps
    the allocator's invariant audit on (``"light"`` samples the full
    invariant audit every 16th mutation and runs a cheap vectorized
    refcount-sum check otherwise).

    Cross-replica serving: with a :class:`SharedPrefixIndex` passed as
    ``prefix_index`` (plus ``kv_prefix_cache=True``), this replica
    publishes its prompt-prefix pages to the index and, on a local trie
    miss, *migrates* a prefix another replica published — the matched
    pages are copied into freshly allocated local pages, registered in
    the local trie, and served under ordinary refcount/CoW semantics.
    See :func:`serve_replicated` for the N-replica harness.

    Overload resilience (``cfg.sata_qos_ladder``): ``load_spike`` /
    ``slow_step`` faults and organic pool pressure (deferrals, stalls)
    step every active slot down the :class:`QoSController` rung ladder
    instead of preempting — the per-slot plan budget/interval/summary
    knobs degrade in place (no re-trace, no requeue) and recover with
    hysteresis once pressure clears.  Without the ladder, a
    ``load_spike`` sheds load the old way: one preemption per severity
    unit.  Every request's report entry records its degradation
    timeline (``out["degradation"]``).

    Checkpoint/resume: with ``checkpoint_dir`` + ``checkpoint_every``,
    the loop atomically saves the device cache and EVERY host-side
    control structure (allocator, trie, swap handles, queue, admission
    order, QoS rungs, counters) at the top of each N-th step;
    ``kill_at_step`` raises :class:`ServeKilled` right after the
    checkpoint block, and a fresh process calling with ``resume=True``
    replays from the last save — outputs bitwise equal to an
    uninterrupted run.

    Host spans (``launch/spans.py``): the report's ``spans`` holds the
    totals of ``serve.setup`` (entry to the first loop iteration),
    ``serve.step`` (one loop iteration) and its children
    ``serve.prefill`` (one claim), ``serve.tables`` (the step's page
    mapping and table push), ``serve.decode`` (the step's dispatch and
    argmax), ``serve.account`` (the step's read and fetch accounting),
    ``serve.emit`` (the per-slot output loop) and ``serve.pull`` (each
    device→host read of the loop, inside the others: one a step — its
    tokens and plan counters in one transfer — plus each claim's first
    token and each retirement sweep's importance read).  ``pulls``
    counts those reads;
    ``queue_wait_s`` is each request's wait from the call to its first
    claim."""
    t_call = time.time()
    spans = Spans()
    setup_span = spans("serve.setup").open()
    opt, res = _fold_serve_legacy(options, resilience, legacy)
    n_requests, batch_slots = opt.n_requests, opt.batch_slots
    gen_len, max_len = opt.gen_len, opt.max_len
    prompt_len, shared_prefix_len = opt.prompt_len, opt.shared_prefix_len
    host_swap_bytes = res.host_swap_bytes
    max_steps_per_request = res.max_steps_per_request
    preempt_retry_limit = res.preempt_retry_limit
    audit_pages = res.audit_pages
    checkpoint_dir, checkpoint_every = res.checkpoint_dir, \
        res.checkpoint_every
    resume, kill_at_step = res.resume, res.kill_at_step
    cfg = cfg or (SMOKE if smoke else ARCHS)[arch]
    mesh = mesh or make_local_mesh()
    if params is None:
        # one jitted program: op-by-op init would keep each layer
        # stack's fp32 draw alive beside the cast params
        params = jax.jit(lambda key: mdl.init_params(key, cfg))(
            jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)

    cache = dec.init_cache(cfg, batch_slots, max_len)
    if cfg.family in ("vlm", "audio"):
        batch_ctx = {}
        if cfg.family == "vlm":
            batch_ctx["image_embeds"] = jnp.asarray(rng.standard_normal(
                (batch_slots, cfg.n_image_tokens, cfg.d_model)), jnp.float32)
        else:
            batch_ctx["audio_embeds"] = jnp.asarray(rng.standard_normal(
                (batch_slots, cfg.encoder_len, cfg.d_model)), jnp.float32)
        cache = dec.prefill_context(params, cfg, cache, batch_ctx)

    step = jax.jit(lambda p, c, t, pos: dec.serve_step(p, cfg, c, t, pos))

    # --- paged-pool allocator (host-side; device consumes the table)
    alloc: Optional[PageAllocator] = None
    from repro.models.layers import _dtype
    if attn.paged_kv_on(cfg):
        page = attn.kv_page_size(cfg, max_len)
        pool = cache.get("kv", cache.get("shared_kv"))
        n_pages = int(pool["k_pages"].shape[1])
        alloc = PageAllocator(n_pages, batch_slots, max_len // page, page,
                              audit=audit_pages)
        alloc.lazy_cow = bool(cfg.kv.lazy_cow)
        cache = dec.set_page_table(cfg, cache, alloc.table)
        # backpressure only helps when at least ONE request's worst-case
        # working set fits: otherwise the livelock handler preempts the
        # sole active slot forever and the run silently truncates
        need_rows = min(max_len, max(1, prompt_len) + gen_len - 1)
        need = alloc.pages_for(need_rows)
        if need > alloc.free_pages:
            raise ValueError(
                f"kv_pool_pages={n_pages} ({alloc.free_pages} usable) "
                f"cannot hold one request's worst-case working set "
                f"({need} pages of {page} tokens) — no schedule can make "
                f"progress; grow the pool or shorten gen_len/max_len")

    # host-swap preemption covers the families whose complete per-slot
    # decode state is pages + plan (dense/moe); recurrent families
    # (hybrid/ssm) carry per-slot mamba/rwkv state the page swap does
    # not capture, so they keep the requeue-and-regenerate path
    can_swap = (alloc is not None and cfg.family in ("dense", "moe")
                and (host_swap_bytes is None or host_swap_bytes > 0))
    if alloc is not None:
        n_layers_kv = int(pool["k_pages"].shape[0])
        swap_page_bytes = (2 * cfg.n_kv_heads * cfg.hd
                           * jnp.dtype(_dtype(cfg)).itemsize
                           * page * n_layers_kv)   # budget estimate/page
    if faults is not None and not faults.empty:
        if alloc is None:
            raise ValueError(
                "fault injection drives the paged allocator — set "
                "kv_cache_layout='paged'")
        if faults.has_crash and not (cfg.family in ("dense", "moe")):
            raise ValueError(
                "crash_step recovery restores every live slot from host "
                "swap, which needs the dense/moe paged serving path")

    # --- prompt prefill (handoff) — dense/moe full-sequence path
    prompt_len = max(1, int(prompt_len))
    use_prefill = prompt_len > 1 and cfg.family in ("dense", "moe")
    if prompt_len > 1 and not use_prefill:
        raise NotImplementedError(
            f"prompt_len > 1 needs the dense/moe prefill path "
            f"(family {cfg.family!r})")
    prefill = (jax.jit(lambda p, t: dec.prefill_prompt(p, cfg, t, max_len))
               if use_prefill else None)
    prefill_tail = (jax.jit(lambda p, t, pk: dec.prefill_prompt(
        p, cfg, t, max_len, prefix_kv=pk)) if use_prefill else None)

    # --- shared-prefix page cache (prompt-prefix trie over the pool)
    pcache: Optional[PrefixCache] = None
    if attn.prefix_cache_on(cfg):
        assert alloc is not None
        pcache = PrefixCache(alloc)
    cow_copies = 0
    page = alloc.page if alloc is not None else max_len
    # --- cross-replica prefix index (see SharedPrefixIndex): publishes
    # ride the local trie register; a local miss consults the index and
    # migrates a remote replica's pages into the local pool
    if prefix_index is not None and pcache is None:
        raise ValueError(
            "prefix_index needs the local shared-prefix cache on "
            "(kv_prefix_cache=True, paged layout) — migration lands "
            "remote pages in the local trie")
    cross_replica_hits = migrated_pages = migrated_tokens = 0
    index_publishes = 0

    def _push_tables():
        nonlocal cache
        # writable_ref_view == ref when lazy CoW is off (bit-identical
        # push); with leases, a live lease's page reports refcount 1 so
        # its holder's in-place appends pass the device write-protect
        cache = dec.set_page_table(
            cfg, cache, alloc.table,
            page_ref=alloc.writable_ref_view() if pcache is not None
            else None)

    def _step_writable(i: int) -> bool:
        """Pool-side gate before slot ``i`` appends at pos_h[i]: CoW
        the target page if it is shared, map it if it is new — evicting
        trie-retained pages first when the pool is dry (cached prefixes
        are a use of SPARE pages, never a reason to stall a live
        request).  False = stall this step."""
        nonlocal cache, cow_copies
        ok, cp = alloc.ensure_writable(i, int(pos_h[i]))
        if not ok and pcache is not None and pcache.evict(1):
            ok, cp = alloc.ensure_writable(i, int(pos_h[i]))
        if not ok:
            return False
        if cp is not None:
            cache = dec.copy_phys_pages(cache, [cp])
            cow_copies += 1
        if alloc.ensure(i, int(pos_h[i])):
            return True
        if pcache is not None and pcache.evict(1):
            return alloc.ensure(i, int(pos_h[i]))
        return False

    # deterministic prompt tokens per request: a request's output
    # depends only on its own prompt, never on which slot served it
    prompts = rng.integers(0, cfg.vocab_size, (n_requests, prompt_len))
    if shared_prefix_len:
        assert shared_prefix_len < prompt_len, "tail must be non-empty"
        prompts[:, :shared_prefix_len] = prompts[0, :shared_prefix_len]
    queue: List[int] = list(range(n_requests))
    outputs: Dict[int, List[int]] = {}
    latency: Dict[int, float] = {}
    t_claim: Dict[int, float] = {}
    queue_wait: Dict[int, float] = {}             # call → first claim
    pulls = 0                        # device→host reads in loop steps
    slots: List[Optional[int]] = [None] * batch_slots
    pos_h = np.zeros(batch_slots, np.int32)       # per-slot positions
    tokens_h = np.zeros((batch_slots, 1), np.int32)
    produced = 0
    steps = 0
    deferred_claims = stalled_steps = preemptions = 0
    fetch_tiles_plan = fetch_tiles_dense = 0
    plan_bytes = kernel_bytes_plan = kernel_bytes_dense = 0
    noted: set = set()               # requests whose hit/miss is counted
    # --- fault-tolerance state
    swapped_recs: Dict[int, Dict[str, Any]] = {}  # request → swap record
    preempt_count: Dict[int, int] = {}
    admit_seq: Dict[int, int] = {}                # request → claim order
    admit_clock = 0
    req_steps: Dict[int, int] = {}                # watchdog: steps held
    timed_out: set = set()
    host_swaps = swap_restores = requeue_preemptions = 0
    tokens_salvaged = requeue_tokens_discarded = re_prefill_tokens = 0
    swap_cold_replans = crashes = protected_admissions = 0
    host_swap_bytes_now = host_swap_bytes_peak = 0
    restore_wall = 0.0
    rep_offset = 0.0              # re-plan count carried across crashes
    # --- overload / integrity state
    corrupt_pages_injected = corrupt_pages_detected = 0
    quarantined_pages = trie_nodes_invalidated = 0
    load_spikes_seen = slow_steps_seen = 0
    degraded_steps = 0
    deferred_retries_skipped = 0
    defer_until: Dict[int, int] = {}      # request → earliest retry step
    defer_backoff: Dict[int, int] = {}    # request → current backoff
    degrade_log: Dict[int, List] = {}     # request → [(step, rung), ...]
    qos_dirty = False
    # --- cascade retirement state
    retire_events = pages_reclaimed = retired_tokens = 0
    retire_log: Dict[int, List] = {}      # request → [(step, pages_freed)]

    def _pull():
        """Span one device→host read of a loop step, and count it."""
        nonlocal pulls
        pulls += 1
        return spans("serve.pull")

    def _clear_backoff() -> None:
        """Pool capacity (may have) grown — deferred claims re-check
        immediately (backoff answers a FULL pool, it is not a fixed
        penalty)."""
        defer_until.clear()
        defer_backoff.clear()

    def _log_rungs(changed: List[int]) -> None:
        """Record rung transitions on the occupying requests' timelines
        and mark the device knob vectors stale."""
        nonlocal qos_dirty
        for i in changed:
            qos_dirty = True
            r = slots[i]
            if r is not None:
                degrade_log.setdefault(r, []).append(
                    (steps, qosctl.rung[i]))

    def _gather_pages(phys):
        return dec.gather_phys_pages(cache, phys)

    def _scatter_pages(fresh, payload):
        nonlocal cache
        cache = dec.scatter_phys_pages(cache, fresh, payload)

    def _payload_bytes(rec) -> int:
        b = sum(a.nbytes for _, payload in rec["handle"]["chunks"]
                for a in payload.values())
        return b + sum(np.asarray(v).nbytes
                       for snap in rec["plan"].values()
                       for v in snap.values())

    def _protected() -> set:
        return {r for r, c in preempt_count.items()
                if c >= preempt_retry_limit}

    def _reserve_need(exclude: Optional[int] = None) -> int:
        """Pages admission must hold back for queued PROTECTED requests
        (at the retry limit): their next re-admission is guaranteed, so
        ordinary claims may not consume the last pages they need."""
        n = 0
        for r in queue:
            if r == exclude or preempt_count.get(r, 0) < preempt_retry_limit:
                continue
            if r in swapped_recs:
                n += alloc.swap_pages_needed(swapped_recs[r]["handle"])
            else:
                n += alloc.pages_for(max(prompt_len, 1))
        return n

    def _swap_out(victim: int) -> None:
        """Host-swap the victim: plan snapshot first (the slot is still
        live), then pages (gather-before-free inside ``swap_out``), then
        release.  Decoded output and position are KEPT — restore
        resumes, it does not regenerate."""
        nonlocal cache, host_swaps, tokens_salvaged
        nonlocal host_swap_bytes_now, host_swap_bytes_peak
        r = slots[victim]
        plan = dec.capture_plan_state(cfg, cache, victim)
        handle = alloc.swap_out(victim, _gather_pages)
        rec = {"handle": handle, "plan": plan,
               "pos": int(pos_h[victim]), "token": int(tokens_h[victim, 0])}
        rec["bytes"] = _payload_bytes(rec)
        swapped_recs[r] = rec
        tokens_salvaged += len(outputs[r])
        queue.insert(0, r)
        slots[victim] = None
        cache = dec.release_slot(cfg, cache, victim)
        host_swaps += 1
        host_swap_bytes_now += rec["bytes"]
        host_swap_bytes_peak = max(host_swap_bytes_peak,
                                   host_swap_bytes_now)

    def _preempt(victim: int) -> None:
        """Evict the victim slot — host-swap when the family supports
        it and the host budget holds the estimated payload, else the
        requeue-and-regenerate fallback (deterministic regeneration
        keeps the final outputs unchanged either way; swap just keeps
        the progress)."""
        nonlocal cache, produced, preemptions, requeue_preemptions
        nonlocal requeue_tokens_discarded
        r = slots[victim]
        preempt_count[r] = preempt_count.get(r, 0) + 1
        est = int(alloc.n_mapped[victim]) * swap_page_bytes
        fits = (host_swap_bytes is None
                or host_swap_bytes_now + est <= host_swap_bytes)
        if can_swap and fits and alloc.n_mapped[victim] > 0:
            _swap_out(victim)
        else:
            produced -= len(outputs[r])       # discarded, not served
            requeue_tokens_discarded += len(outputs[r])
            outputs[r] = []
            queue.insert(0, r)
            slots[victim] = None
            cache = dec.release_slot(cfg, cache, victim)
            alloc.free_slot(victim)
            requeue_preemptions += 1
        preemptions += 1
        _clear_backoff()                  # the victim's pages freed

    def _crash_restore() -> None:
        """Mid-serve crash: every byte the device holds is about to be
        lost, so (1) outstanding swap handles convert their resident
        shared pages to host payload, (2) every live slot full-swaps to
        host, then (3) the device cache, allocator, and (empty) prefix
        trie rebuild from scratch and the claim loop re-admits each
        request from its swap handle — positions, plan state, and
        decoded output all survive."""
        nonlocal cache, alloc, pcache, crashes, last_rep, rep_base
        nonlocal rep_offset, host_swap_bytes_now, host_swap_bytes_peak
        for rec in swapped_recs.values():
            alloc.swap_to_full(rec["handle"], _gather_pages)
            nb = _payload_bytes(rec)
            host_swap_bytes_now += nb - rec["bytes"]
            rec["bytes"] = nb
        # reversed: each insert(0) lands the lowest slot at the queue
        # head, so re-admission replays in slot order
        for i in reversed(range(batch_slots)):
            r = slots[i]
            if r is not None:
                _swap_out(i)                  # crash ignores the budget
                rec = swapped_recs[r]
                alloc.swap_to_full(rec["handle"], _gather_pages)
                nb = _payload_bytes(rec)
                host_swap_bytes_now += nb - rec["bytes"]
                rec["bytes"] = nb
        host_swap_bytes_peak = max(host_swap_bytes_peak,
                                   host_swap_bytes_now)
        handles = alloc.swapped
        squeezed_n = len(alloc.squeezed)
        # device teardown + rebuild (same shapes — the jitted step's
        # trace still applies)
        cache = dec.init_cache(cfg, batch_slots, max_len)
        alloc = PageAllocator(n_pages, batch_slots, max_len // page, page,
                              audit=audit_pages)
        alloc.swapped = handles               # payload survives the crash
        alloc.squeeze(squeezed_n)             # injected pressure persists
        if pcache is not None:
            old = pcache
            pcache = PrefixCache(alloc)       # trie contents are lost...
            pcache.hits, pcache.misses = old.hits, old.misses
            pcache.tokens_saved = old.tokens_saved      # ...stats carry
            pcache.evictions = old.evictions
        for i in range(batch_slots):
            cache = dec.release_slot(cfg, cache, i)
        _push_tables()
        # fold the pre-crash re-plan count into the offset; the fresh
        # cache's counters restart the delta accounting
        if last_rep is not None:
            rep_offset += float((last_rep - rep_base).mean())
            last_rep = _plan_replans(_plan_field(cache, "replans"))
            rep_base = last_rep.copy()
        crashes += 1
    from repro.kernels.ops import decode_fetch_stats
    blk = attn.decode_block_size(cfg, max_len)
    tile_bytes = 2 * blk * cfg.hd * jnp.dtype(_dtype(cfg)).itemsize

    # --- SLO degradation ladder over the per-slot plan knob vectors
    qosctl: Optional[QoSController] = None
    if cfg.sata.qos.ladder:
        has_qos_plan = any(
            isinstance(cache.get(n), dict) and "plan" in cache[n]
            and "budget" in cache[n]["plan"] for n in ("kv", "shared_kv"))
        if not has_qos_plan:
            raise ValueError(
                "sata_qos_ladder degrades the SATA decode plan — turn on "
                "sata_decode routing (the cache carries no qos plan)")
        nkb0 = max_len // blk
        p0 = cfg.sata.decode.blocks or nkb0
        qosctl = QoSController(
            batch_slots, p0=min(int(p0), nkb0),
            iv0=attn._resolve_replan(cfg)[0],
            clear_steps=cfg.sata.qos.clear_steps)

    # --- cascade token retirement (SpAtten): free cold blocks' pages
    # back to the pool MID-STREAM instead of holding every prefix token
    # until completion.  Lossy by design once a pass fires; "off" keeps
    # the whole stack bitwise identical (no plan fields, no passes).
    retire_on = cfg.sata.retire.mode == "on"
    if retire_on:
        if alloc is None or _plan_field(cache, "imp") is None:
            raise ValueError(
                "sata_retire='on' frees pages through the paged allocator "
                "and ranks blocks by the decode plan's importance "
                "accumulator — it needs kv_cache_layout='paged' AND sata "
                "decode routing")
        retire_keep = float(cfg.sata.retire.keep)
        retire_mark = float(cfg.sata.retire.watermark)

    def _retire_pass(force: bool) -> bool:
        """One cascade-retirement sweep: for every active slot past its
        live-token watermark (``force`` — pool pressure this step —
        sweeps every slot), retire the coldest completed blocks down to
        the ``sata_retire_keep`` budget and free their pages.

        Importance = the plan's exponentially-decayed selection
        accumulator (``plan["imp"]``), summed over layers and kv heads
        — the SpAtten cumulative-attention signal, proxied by the score
        pass's own selection output so it costs zero extra cache reads.
        Never candidates: the current append block (and anything after
        it), already-retired holes; ``retire_compact`` additionally
        skips pinned pages (trie-shared / other-slot / swap-resident
        refs).  Survivors keep their logical positions — the plan
        repair (``dec.retire_plan``) only unnames the dead blocks, so
        causality masks and RoPE are untouched.  Returns True when any
        page was freed (caller re-pushes tables + clears backoff)."""
        nonlocal cache, retire_events, pages_reclaimed, retired_tokens
        imp = None
        freed_any = False
        for i in range(batch_slots):
            r = slots[i]
            if r is None:
                continue
            ret = alloc.retired[i]
            live_tok = int(pos_h[i]) + 1 - page * len(ret)
            if not (force or live_tok >= retire_mark * max_len):
                continue
            cur_blk = int(pos_h[i]) // page
            live_lps = [lp for lp in range(int(alloc.n_mapped[i]))
                        if lp not in ret]
            cand = [lp for lp in live_lps if lp < cur_blk]
            keep_n = max(1, int(np.ceil(retire_keep * len(live_lps))))
            n_ret = min(len(live_lps) - keep_n, len(cand))
            if n_ret <= 0:
                continue
            if imp is None:                  # one device pull per sweep
                with _pull():
                    a = np.asarray(_plan_field(cache, "imp"))
                imp = a.reshape(-1, *a.shape[-3:])     # (L, B, KV, nkb)
            score = imp[:, i].sum(axis=(0, 1))         # (nkb,)
            # coldest first; ties retire the OLDEST block (deterministic)
            cand.sort(key=lambda lp: (float(score[lp]), lp))
            chosen = cand[:n_ret]
            freed, skipped = alloc.retire_compact(i, chosen)
            retired_lps = [lp for lp in chosen if lp not in skipped]
            if not retired_lps:
                continue                     # every candidate was pinned
            cache = dec.retire_plan(cfg, cache, i, retired_lps)
            if freed:
                cache = dec.retire_phys_pages(cache, freed)
                freed_any = True
            retire_events += 1
            pages_reclaimed += len(freed)
            retired_tokens += page * len(retired_lps)
            retire_log.setdefault(r, []).append((steps, len(freed)))
        return freed_any

    # every slot starts RELEASED (no request → no re-plan beat, no
    # accounting); a claim re-activates it through reset_slot
    for i in range(batch_slots):
        cache = dec.release_slot(cfg, cache, i)
    # warm the jit trace before any latency clock starts — every slot a
    # request claims is reset first (paged: the unmapped tables route
    # the warm-up writes to the overflow page), so the warm-up never
    # reaches an output
    logits, cache = step(params, cache, jnp.asarray(tokens_h),
                         jnp.asarray(pos_h))
    jax.block_until_ready(logits)
    # the baseline skips warm-up's re-plan
    last_rep = _plan_replans(_plan_field(cache, "replans"))
    rep_base = None if last_rep is None else last_rep.copy()

    def _ctrs():
        """Counter snapshot for the checkpoint meta blob — restore
        unpacks the SAME order (keep the two sites in sync)."""
        return (produced, deferred_claims, stalled_steps, preemptions,
                fetch_tiles_plan, fetch_tiles_dense, plan_bytes,
                kernel_bytes_plan, kernel_bytes_dense, host_swaps,
                swap_restores, requeue_preemptions, tokens_salvaged,
                requeue_tokens_discarded, re_prefill_tokens,
                swap_cold_replans, crashes, protected_admissions,
                host_swap_bytes_now, host_swap_bytes_peak, restore_wall,
                rep_offset, cow_copies, corrupt_pages_injected,
                corrupt_pages_detected, quarantined_pages,
                trie_nodes_invalidated, load_spikes_seen, slow_steps_seen,
                degraded_steps, deferred_retries_skipped,
                retire_events, pages_reclaimed, retired_tokens)

    # --- cross-process serve checkpoint/resume
    ckpt = None
    if checkpoint_dir is not None:
        from repro.checkpoint.manager import CheckpointManager
        ckpt = CheckpointManager(checkpoint_dir, keep=2)
    last_ckpt = -1
    resumed_at: Optional[int] = None
    if resume:
        assert ckpt is not None, "resume=True needs checkpoint_dir"
        rstep = ckpt.latest_step()
        assert rstep is not None, "resume=True but no checkpoint on disk"
        cache = ckpt.restore(like=cache, step=rstep)
        m = pickle.loads(ckpt.load_meta(rstep))
        steps = m["steps"]
        last_ckpt = resumed_at = steps
        queue = m["queue"]
        outputs = m["outputs"]
        latency = m["latency"]
        slots = m["slots"]
        pos_h = m["pos_h"]
        tokens_h = m["tokens_h"]
        alloc = m["alloc"]
        pcache = m["pcache"]
        swapped_recs = m["swapped_recs"]
        preempt_count = m["preempt_count"]
        admit_seq = m["admit_seq"]
        admit_clock = m["admit_clock"]
        req_steps = m["req_steps"]
        timed_out = m["timed_out"]
        noted = m["noted"]
        qosctl = m["qosctl"]
        degrade_log = m["degrade_log"]
        retire_log = m.get("retire_log", {})
        defer_until = m["defer_until"]
        defer_backoff = m["defer_backoff"]
        last_rep = m["last_rep"]
        rep_base = m["rep_base"]
        rng.bit_generator.state = m["rng"]
        (produced, deferred_claims, stalled_steps, preemptions,
         fetch_tiles_plan, fetch_tiles_dense, plan_bytes,
         kernel_bytes_plan, kernel_bytes_dense, host_swaps,
         swap_restores, requeue_preemptions, tokens_salvaged,
         requeue_tokens_discarded, re_prefill_tokens,
         swap_cold_replans, crashes, protected_admissions,
         host_swap_bytes_now, host_swap_bytes_peak, restore_wall,
         rep_offset, cow_copies, corrupt_pages_injected,
         corrupt_pages_detected, quarantined_pages,
         trie_nodes_invalidated, load_spikes_seen, slow_steps_seen,
         degraded_steps, deferred_retries_skipped,
         retire_events, pages_reclaimed, retired_tokens) = m["ctrs"]
        # wall clocks re-anchor — resumed latencies measure THIS
        # process's wall; outputs/counters stay bitwise
        t_claim = {r: time.time() for r in m["t_claim_reqs"]}
        if alloc is not None:
            _push_tables()
    t0 = time.time()
    # paged backpressure can stall slots / defer claims / preempt-and-
    # restart, so budget extra lockstep steps beyond the contiguous-
    # layout worst case
    max_steps = 4 * (n_requests * gen_len + batch_slots + 1)
    setup_span.close()
    while (queue or any(s is not None for s in slots)) and steps < max_steps:
        step_span = spans.step("serve.step", steps).open()
        if (ckpt is not None and checkpoint_every > 0
                and steps % checkpoint_every == 0 and steps != last_ckpt):
            meta = {
                "steps": steps, "queue": list(queue), "outputs": outputs,
                "latency": latency, "slots": list(slots),
                "pos_h": pos_h.copy(), "tokens_h": tokens_h.copy(),
                "alloc": alloc, "pcache": pcache,
                "swapped_recs": swapped_recs,
                "preempt_count": preempt_count, "admit_seq": admit_seq,
                "admit_clock": admit_clock, "req_steps": req_steps,
                "timed_out": timed_out, "noted": noted,
                "qosctl": qosctl, "degrade_log": degrade_log,
                "retire_log": retire_log,
                "defer_until": defer_until, "defer_backoff": defer_backoff,
                "last_rep": last_rep, "rep_base": rep_base,
                "rng": rng.bit_generator.state,
                "t_claim_reqs": list(t_claim), "ctrs": _ctrs(),
            }
            # ONE pickle: alloc.swapped, the trie's allocator back-
            # pointer, and every swap record's handle keep their shared
            # identity through the dump (swap_in asserts on it)
            ckpt.save(steps, cache, blocking=True,
                      meta_blob=pickle.dumps(meta))
            last_ckpt = steps
        if kill_at_step is not None and steps == kill_at_step:
            step_span.close()
            raise ServeKilled(f"injected process kill at loop step {steps}")
        defer_now = False
        pressure_now = False
        if faults is not None:                    # injected adversity
            for kind, arg in faults.at(steps):
                if kind == "pool_squeeze":
                    alloc.squeeze(arg)
                elif kind == "pool_restore":
                    alloc.unsqueeze(arg)
                    _clear_backoff()              # capacity returned
                elif kind == "load_spike":
                    load_spikes_seen += 1
                    sev = 1 if arg is None else max(1, int(arg))
                    held = [j for j in range(batch_slots)
                            if slots[j] is not None]
                    if qosctl is not None:
                        # shed QUALITY, not requests: every active slot
                        # steps down `severity` rungs in place
                        _log_rungs(qosctl.press(held, sev))
                        pressure_now = True
                    else:
                        # no ladder — shed load the old way: one
                        # preemption per severity unit
                        for _ in range(sev):
                            held = [j for j in range(batch_slots)
                                    if slots[j] is not None]
                            if not held:
                                break
                            _preempt(_pick_victim(held, slots, outputs,
                                                  admit_seq, _protected()))
                            _push_tables()
                elif kind == "slow_step":
                    slow_steps_seen += 1
                    if qosctl is not None:        # deadline pressure
                        held = [j for j in range(batch_slots)
                                if slots[j] is not None]
                        _log_rungs(qosctl.press(held, 1))
                        pressure_now = True
                elif kind == "corrupt_page":
                    # flip one byte in the nth outstanding swap handle's
                    # first parked chunk (deterministic offset) — the
                    # checksum verify at swap-in must catch it
                    recs = sorted(swapped_recs)
                    if recs:
                        nth = 0 if arg is None else int(arg)
                        rec_c = swapped_recs[recs[nth % len(recs)]]
                        chunks = rec_c["handle"]["chunks"]
                        if chunks:
                            _, payload = chunks[0]
                            key = sorted(payload)[0]
                            # parked payloads can be read-only device
                            # views — corrupt a writable copy IN the
                            # payload dict (handle identity unchanged)
                            arr = np.array(payload[key])   # owning copy
                            payload[key] = arr
                            flat = arr.view(np.uint8).reshape(-1)
                            flat[(steps * 131 + nth) % flat.size] ^= 0x01
                            corrupt_pages_injected += 1
                elif kind == "defer_admission":
                    defer_now = True
                elif kind == "preempt":
                    tgt = arg
                    if tgt is None:
                        held = [j for j in range(batch_slots)
                                if slots[j] is not None]
                        tgt = (_pick_victim(held, slots, outputs,
                                            admit_seq, _protected())
                               if held else None)
                    if tgt is not None and slots[tgt] is not None:
                        _preempt(tgt)
                        _push_tables()
                elif kind == "crash_step":
                    _crash_restore()
        for i in range(batch_slots):              # claim free slots
            if slots[i] is not None or not queue or defer_now:
                continue
            r0 = queue[0]
            if steps < defer_until.get(r0, 0):
                # bounded deferred-admission backoff: a claim the pool
                # rejected re-checks at its scheduled step instead of
                # every step; the break keeps later queue entries
                # BEHIND the head (admission-order fair)
                deferred_retries_skipped += 1
                break
            if r0 in swapped_recs:
                # integrity gate BEFORE any page is reserved: a handle
                # corrupted while parked on the host must never scatter
                # into the pool
                try:
                    alloc.verify_handle(swapped_recs[r0]["handle"])
                except PageIntegrityError:
                    # quarantine: drop the handle, invalidate trie
                    # entries over its resident pages, and recover the
                    # victim by deterministic re-prefill below (its
                    # salvaged progress is lost with the payload)
                    rec = swapped_recs.pop(r0)
                    quarantined_pages += sum(
                        len(lps) for lps, _ in rec["handle"]["chunks"])
                    bad = alloc.discard_handle(rec["handle"])
                    if pcache is not None and bad:
                        trie_nodes_invalidated += \
                            pcache.invalidate_pages(bad)
                    host_swap_bytes_now -= rec["bytes"]
                    corrupt_pages_detected += 1
                    produced -= len(outputs[r0])
                    requeue_tokens_discarded += len(outputs[r0])
                    tokens_salvaged -= len(outputs[r0])   # salvage failed
                    outputs[r0] = []
            r0_protected = preempt_count.get(r0, 0) >= preempt_retry_limit
            # protected requests (at the retry limit) consume the
            # reserve admission holds back for them; everyone else
            # must leave it untouched
            reserve = (0 if (alloc is None or r0_protected)
                       else _reserve_need(exclude=r0))
            if r0 in swapped_recs:
                # --- re-admission from host swap: restore, not redo —
                # pages scatter back, the plan reinstalls reset-free,
                # and decode resumes at the swapped position with the
                # swapped next-token (outputs so far were kept)
                rec = swapped_recs[r0]
                needed = alloc.swap_pages_needed(rec["handle"]) + reserve
                if not alloc.can_admit(needed):
                    if pcache is not None:
                        pcache.evict(needed)
                    if not alloc.can_admit(needed):
                        deferred_claims += 1      # backpressure: wait
                        bo = min(max(defer_backoff.get(r0, 0) * 2, 1), 8)
                        defer_backoff[r0] = bo
                        defer_until[r0] = steps + bo
                        pressure_now = True
                        break
                t_res = time.time()
                ok = alloc.swap_in(i, rec["handle"], _scatter_pages)
                assert ok, "can_admit reserved the payload pages"
                cache = dec.restore_plan_state(cfg, cache, i, rec["plan"])
                _push_tables()
                queue.pop(0)
                slots[i] = r0
                admit_seq[r0] = admit_clock
                admit_clock += 1
                defer_until.pop(r0, None)
                defer_backoff.pop(r0, None)
                if qosctl is not None and qosctl.reset(i):
                    qos_dirty = True              # fresh episode: rung 0
                pos_h[i] = rec["pos"]
                tokens_h[i, 0] = rec["token"]
                snap = (rec["plan"].get("kv")
                        or rec["plan"].get("shared_kv"))
                if last_rep is not None:
                    if rec["pos"] > 0 and (
                            snap is None
                            or not np.asarray(snap.get("active",
                                                       True)).any()):
                        swap_cold_replans += 1    # structurally 0 when
                        #     capture/restore moved a live plan intact
                    if snap is not None and "replans" in snap:
                        # the device counter at slot i jumps to the
                        # restored value — absorb the jump into the
                        # baseline so it never counts as a re-plan
                        col = snap["replans"].astype(
                            np.float64).reshape(-1)
                        rep_base[:, i] += col - last_rep[:, i]
                        last_rep[:, i] = col
                host_swap_bytes_now -= rec["bytes"]
                del swapped_recs[r0]
                swap_restores += 1
                if r0_protected:
                    protected_admissions += 1
                restore_wall += time.time() - t_res
                continue
            # prefix match BEFORE admission: a matched prefix maps
            # cached pages, so it shrinks the claim's pool demand
            # (match tokens[:-1] — the tail must stay non-empty so
            # the prefill always produces last-token logits)
            m, phys_m = 0, []
            mig: Optional[Tuple[int, Dict[str, np.ndarray], int]] = None
            if pcache is not None and use_prefill:
                m, phys_m, _ = pcache.match(prompts[r0, :-1])
                if prefix_index is not None:
                    hit = prefix_index.lookup(replica_id,
                                              prompts[r0, :-1])
                    # migrate only when another replica's publication
                    # beats the local trie — re-importing this
                    # replica's own (evicted) pages is just a re-prefill
                    if hit is not None and hit[0] > m and hit[2] > 0:
                        mig = hit
            if alloc is not None:
                def _need():
                    if mig is not None:
                        # migrated pages are fresh local COPIES — the
                        # claim pays for every prompt page (the win is
                        # prefill compute, not pool pages)
                        return alloc.pages_for(max(prompt_len, 1))
                    return max(alloc.pages_for(max(prompt_len, 1))
                               - len(phys_m) + (1 if m % page else 0),
                               0)
                if not alloc.can_admit(_need() + reserve):
                    if pcache is not None:
                        pcache.evict(_need() + reserve)
                        # eviction may have dropped matched pages —
                        # re-walk before trusting the mapping
                        m, phys_m, _ = pcache.match(
                            prompts[r0, :-1])
                    if mig is not None and \
                            not alloc.can_admit(_need() + reserve):
                        # a migration is optional work — under pool
                        # pressure fall back to the plain (cheaper)
                        # admission before deferring
                        mig = None
                    if not alloc.can_admit(_need() + reserve):
                        deferred_claims += 1  # backpressure: wait
                        bo = min(max(defer_backoff.get(r0, 0) * 2, 1), 8)
                        defer_backoff[r0] = bo
                        defer_until[r0] = steps + bo
                        pressure_now = True
                        break
            r = queue.pop(0)
            claim_span = spans("serve.prefill", request=r).open()
            slots[i] = r
            admit_seq[r] = admit_clock
            admit_clock += 1
            defer_until.pop(r, None)
            defer_backoff.pop(r, None)
            if qosctl is not None and qosctl.reset(i):
                qos_dirty = True                  # fresh episode: rung 0
            if r0_protected:
                protected_admissions += 1
            if preempt_count.get(r, 0) and use_prefill:
                re_prefill_tokens += prompt_len - m   # requeue redoes it
            outputs[r] = []
            t_claim[r] = time.time()          # claim → last token
            queue_wait.setdefault(r, t_claim[r] - t_call)
            cache = dec.reset_slot(cfg, cache, i)
            if use_prefill:
                if pcache is not None and r not in noted:
                    # once per REQUEST: a preempted request's
                    # re-claim would otherwise double-count (its
                    # own registered pages guarantee the re-claim
                    # hits, inflating saved past total)
                    noted.add(r)
                    pcache.note(mig[0] if mig is not None else m)
                if mig is not None:
                    # cross-replica page migration: copy the remote
                    # replica's published prefix pages into freshly
                    # allocated LOCAL pages, register them in the local
                    # trie, and continue exactly like a local full-page
                    # hit (the slot owns the pages; the trie's register
                    # adds its retention ref, so CoW semantics from
                    # here on are the ordinary owner-after-register
                    # case)
                    rows, payload, _n_rem = mig
                    npg = rows // page
                    ok = alloc.ensure(i, rows - 1)
                    assert ok, "admission control reserved these pages"
                    phys_mig = [int(p_) for p_ in alloc.table[i, :npg]]
                    cache = dec.scatter_phys_pages(cache, phys_mig,
                                                   payload)
                    pcache.register(prompts[r, :rows], alloc.table[i])
                    _push_tables()
                    cross_replica_hits += 1
                    migrated_pages += npg
                    migrated_tokens += rows
                    prefix_index.remote_hits += 1
                    m, phys_m = rows, []   # slot already maps the pages
                if m and phys_m:
                    alloc.map_shared(i, phys_m)
                    if m % page:
                        # the tail's first rows land inside the
                        # last matched page: shared → CoW now
                        ok, cp = alloc.ensure_writable(i, m)
                        assert ok, "admission reserved the CoW page"
                        if cp is not None:
                            cache = dec.copy_phys_pages(cache, [cp])
                            cow_copies += 1
                if alloc is not None:
                    ok = alloc.ensure(i, prompt_len - 1)
                    assert ok, "admission control reserved these pages"
                    _push_tables()
                if m:
                    prefix = dec.gather_prefix_kv(cache,
                                                  alloc.table[i], m)
                    lg0, state = prefill_tail(
                        params,
                        jnp.asarray(prompts[r:r + 1, m:], jnp.int32),
                        prefix)
                else:
                    lg0, state = prefill(params, jnp.asarray(
                        prompts[r:r + 1], jnp.int32))
                phys = (alloc.table[i, :alloc.pages_for(prompt_len)]
                        if alloc is not None else None)
                cache = dec.install_prefill(cfg, cache, i, state, phys,
                                            prefix_len=m)
                if pcache is not None:
                    # retain the prompt's pages (full pages chain
                    # the trie; the final partial page becomes a
                    # terminal node, so the owner's own first
                    # append below will copy-on-write it)
                    pcache.register(prompts[r], alloc.table[i])
                    _push_tables()
                    if prefix_index is not None:
                        # publish the MATCHABLE full pages (matchers
                        # walk tokens[:-1]); full prompt pages are
                        # append-frozen under trie retention, so the
                        # host copy taken here stays valid forever
                        full = ((prompt_len - 1) // page) * page
                        if full:
                            npg_f = full // page
                            payload_f = dec.gather_phys_pages(
                                cache,
                                [int(p_) for p_
                                 in alloc.table[i, :npg_f]])
                            index_publishes += prefix_index.publish(
                                replica_id, prompts[r, :full], page,
                                payload_f)
                pos_h[i] = prompt_len
                # the prefill's last-position argmax IS the first
                # generated token — record it, don't just feed it
                with _pull():
                    first = int(jnp.argmax(lg0[0]))
                outputs[r].append(first)
                produced += 1
                tokens_h[i, 0] = first
                if len(outputs[r]) >= gen_len or pos_h[i] >= max_len:
                    latency[r] = time.time() - t_claim[r]
                    slots[i] = None           # gen_len=1: done already
                    cache = dec.release_slot(cfg, cache, i)
                    if alloc is not None:
                        alloc.free_slot(i)
                        _clear_backoff()
            else:
                pos_h[i] = 0
                tokens_h[i, 0] = int(prompts[r, 0])
            claim_span.close()
        active = [i for i in range(batch_slots) if slots[i] is not None]
        stalled: List[int] = []
        if alloc is not None and active:
            tables_span = spans("serve.tables").open()
            while True:
                stalled = [i for i in active if slots[i] is not None
                           and not _step_writable(i)]
                runnable = [i for i in active if slots[i] is not None
                            and i not in stalled]
                if not stalled or runnable:
                    break
                # every active slot is stalled: first reclaim pages only
                # the prefix trie still holds, then — pages only free
                # when a request completes — livelock.  Preempt the
                # least-progress victim (``_pick_victim``; admission
                # order breaks ties, protected requests are spared):
                # host-swap keeps its decoded progress when the family
                # and host budget allow, requeue regenerates it —
                # either way deterministic decode leaves the final
                # outputs unchanged, and shared pages survive through
                # their other references.
                if pcache is not None and pcache.evict(1):
                    continue
                victim = _pick_victim(stalled, slots, outputs, admit_seq,
                                      _protected())
                _preempt(victim)
            stalled_steps += len(stalled)
            _push_tables()
            # preemption may have freed slots out of the stale list
            active = [i for i in range(batch_slots) if slots[i] is not None]
            tables_span.close()
        if qosctl is not None:
            if stalled:
                pressure_now = True               # organic pool pressure
            # hysteresis clock ticks once per step, then the (possibly
            # changed) knob vectors push BEFORE this step's compute —
            # values only, so the jitted trace is untouched
            _log_rungs(qosctl.tick(active, pressure_now))
            degraded_steps += sum(1 for i in active if qosctl.rung[i] > 0)
            if qos_dirty:
                cache = dec.set_qos_knobs(cache, *qosctl.vectors())
                qos_dirty = False
        with spans("serve.decode"):
            logits, cache = step(params, cache, jnp.asarray(tokens_h),
                                 jnp.asarray(pos_h))
            nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
        account_span = spans("serve.account").open()
        # the step's one device→host read: its tokens and, with a plan,
        # the plan's occupancy and re-plan counters, in one transfer
        with _pull():
            nxt_h, cnt, rep = jax.device_get(
                (nxt, _plan_field(cache, "kv_counts"),
                 _plan_field(cache, "replans")))
        counts, rep = _plan_counts(cnt), _plan_replans(rep)
        live = [i for i in active if i not in stalled]
        frac = 0.0
        if rep is not None:
            # per-(layer, slot) delta, attributed to LIVE slots only —
            # released slots never fire, and an idle slot's counter
            # must not dilute or inflate the live traffic blend.
            # Kept per slot (mean over layers): the partial re-plan
            # streams only the triggering slots' caches, so each live
            # slot is charged its own full/incremental blend
            delta = np.clip(rep - last_rep, 0.0, 1.0)
            last_rep = rep
            if live:
                frac = delta[:, live].mean(axis=0)           # (B_live,)
        if counts is not None and live:
            # count only slots holding live requests — idle slots still
            # run through the lockstep batch but serve nobody
            pb = cfg.sata.decode.blocks
            qn = sk = None
            if qosctl is not None:
                # mixed rungs: price each live slot at ITS degraded
                # budget / summary backend / re-plan mode, or the
                # reported savings overstate what degraded slots fetch
                kn = [qosctl.knobs(i) for i in live]
                pb = np.asarray([k[0] for k in kn], np.int64)
                qn = np.asarray([k[2] for k in kn], bool)
                sk = np.asarray([k[3] for k in kn], bool)
            lv = None
            if retire_on:
                # retired blocks left the ranking set — summary reads
                # and re-plan key streams price at the live count
                lv = np.asarray(
                    [max_len // blk - len(alloc.retired[i]) for i in live],
                    np.int64)
            st = decode_fetch_stats(counts[:, live], pos_h[live],
                                    k_block=blk, d=cfg.hd, replan=frac,
                                    nkb=max_len // blk,
                                    dtype_bytes=jnp.dtype(
                                        _dtype(cfg)).itemsize,
                                    summary=cfg.sata.decode.summary,
                                    replan_mode=cfg.sata.decode.replan_mode,
                                    sketch_factor=(
                                        cfg.sata.decode.sketch_factor),
                                    plan_blocks=pb, quant=qn, sketch=sk,
                                    live_blocks=lv)
            fetch_tiles_plan += st["kv_fetch_tiles_plan"]
            fetch_tiles_dense += st["kv_fetch_tiles_dense"]
            plan_bytes += st["plan_fetch_bytes_step"]
            kernel_bytes_plan += st["kv_fetch_bytes_plan"]
            kernel_bytes_dense += st["kv_fetch_bytes_dense"]
        account_span.close()
        emit_span = spans("serve.emit").open()
        now = time.time()
        for i in range(batch_slots):
            r = slots[i]
            if r is None:
                continue
            # watchdog clock: every step HOLDING the slot counts,
            # stalled or not — a runaway request must not sit on pool
            # pages forever just because it also stalls
            req_steps[r] = req_steps.get(r, 0) + 1
            if i not in stalled:                  # stalled: re-fed as-is
                outputs[r].append(int(nxt_h[i]))
                produced += 1
                pos_h[i] += 1
            done = len(outputs[r]) >= gen_len or pos_h[i] >= max_len
            expired = (max_steps_per_request is not None and not done
                       and req_steps[r] >= max_steps_per_request)
            if done or expired:
                latency[r] = now - t_claim[r]
                if expired:
                    # graceful retirement: partial output stands, pages
                    # free, the request is NOT requeued
                    timed_out.add(r)
                slots[i] = None                   # finished → free slot
                cache = dec.release_slot(cfg, cache, i)
                if alloc is not None:
                    alloc.free_slot(i)            # … and its pages
                    _clear_backoff()
            elif i not in stalled:
                tokens_h[i, 0] = nxt_h[i]
        emit_span.close()
        if retire_on:
            # after the step: this step's selection is already folded
            # into the importance accumulator, and completed slots have
            # released — pool pressure (a deferral, a stall, a spike)
            # forces a sweep of every active slot, the watermark fires
            # per slot otherwise
            if _retire_pass(pressure_now or bool(stalled)):
                _push_tables()
                _clear_backoff()              # freed pages: re-check now
        steps += 1
        step_span.close()
    dt = time.time() - t0
    out: Dict[str, Any] = {
        "outputs": outputs, "tokens_generated": produced,
        "tok_per_s": produced / max(dt, 1e-9), "steps": steps,
        "pulls": pulls, "spans": spans.report(),
        "queue_wait_s": queue_wait,
        "request_latency_s": latency,
        "latency_mean_s": float(np.mean(list(latency.values())))
        if latency else 0.0,
        "timed_out": sorted(timed_out),
    }
    # per-request degradation timeline: every (step, rung) transition of
    # the slot while this request held it — empty means the request was
    # served at full quality end to end
    out["degradation"] = {r: list(degrade_log.get(r, [])) for r in outputs}
    if retire_on:
        # per-request retirement timelines ((step, pages_freed) per
        # pass) plus SpAtten's second cascade, report-only: per-KV-head
        # importance (the decayed accumulator summed over layers, slots
        # and blocks) — the signal a future head-pruning cascade would
        # rank on, surfaced with zero behavior change
        a = np.asarray(_plan_field(cache, "imp"))
        head_imp = a.reshape(-1, *a.shape[-3:]).sum(axis=(0, 1, 3))
        out["retirement"] = {
            "events": retire_events,
            "pages_reclaimed": pages_reclaimed,
            "retired_tokens": retired_tokens,
            "timelines": {r: list(retire_log.get(r, [])) for r in outputs},
            "head_importance": [float(x) for x in head_imp],
            "keep_budget": retire_keep,
            "watermark": retire_mark,
        }
    if qosctl is not None:
        out["qos"] = {
            "rung_downs": qosctl.rung_downs,
            "rung_ups": qosctl.rung_ups,
            "degraded_steps": degraded_steps,
            "load_spikes": load_spikes_seen,
            "slow_steps": slow_steps_seen,
            "clear_steps": qosctl.clear_steps,
            "final_rungs": list(qosctl.rung),
        }
    if ckpt is not None:
        out["checkpoint"] = {"dir": str(checkpoint_dir),
                             "last_saved_step": last_ckpt,
                             "resumed_at": resumed_at}
    if fetch_tiles_dense:
        out["decode_fetch"] = {
            "kv_fetch_tiles_plan": fetch_tiles_plan,
            "kv_fetch_tiles_dense": fetch_tiles_dense,
            "kv_fetch_bytes_plan": fetch_tiles_plan * tile_bytes,
            "kv_fetch_bytes_dense": fetch_tiles_dense * tile_bytes,
            "fetch_reduction": fetch_tiles_dense / max(fetch_tiles_plan, 1),
            # plan-side (selection) traffic — exact full re-plans
            # stream all cached K, sketch re-plans only the surviving
            # candidate blocks, incremental steps read the summaries
            # (fp32 bounds or int8 codes+scale/zero) + planned keys;
            # true_reduction is per-backend honest because the summary
            # bytes above are sized by the configured backend
            "plan_fetch_bytes": plan_bytes,
            "summary_backend": cfg.sata.decode.summary,
            "replan_mode": cfg.sata.decode.replan_mode,
            "step_bytes_plan_route": kernel_bytes_plan + plan_bytes,
            "step_bytes_dense_route": kernel_bytes_dense,
            "true_reduction": kernel_bytes_dense
            / max(kernel_bytes_plan + plan_bytes, 1),
            "replans": rep_offset + float((last_rep - rep_base).mean()),
        }
    if alloc is not None:
        layers = int(jax.tree_util.tree_leaves(
            cache.get("kv", cache.get("shared_kv")))[0].shape[0])
        row_bytes = 2 * cfg.n_kv_heads * cfg.hd \
            * jnp.dtype(_dtype(cfg)).itemsize
        occ = alloc.stats(row_bytes=row_bytes, layers=layers)
        occ["contiguous_reserved_bytes"] = \
            batch_slots * max_len * row_bytes * layers
        occ["reserved_vs_contiguous"] = (
            occ["contiguous_reserved_bytes"]
            / max(occ["hbm_reserved_bytes"], 1))
        occ["deferred_claims"] = deferred_claims
        occ["stalled_steps"] = stalled_steps
        occ["preemptions"] = preemptions
        # fault-tolerance counters: swap preserves progress, requeue
        # discards it; crash restores everything from host swap
        occ["host_swaps"] = host_swaps
        occ["swap_restores"] = swap_restores
        occ["requeue_preemptions"] = requeue_preemptions
        occ["tokens_salvaged"] = tokens_salvaged
        occ["requeue_tokens_discarded"] = requeue_tokens_discarded
        occ["re_prefill_tokens"] = re_prefill_tokens
        occ["swap_cold_replans"] = swap_cold_replans
        occ["host_swap_bytes_peak"] = host_swap_bytes_peak
        occ["swap_restore_wall_s"] = restore_wall
        occ["crashes"] = crashes
        occ["preempt_retries_max"] = max(preempt_count.values(), default=0)
        occ["preempted_requests"] = sum(
            1 for c in preempt_count.values() if c > 0)
        occ["protected_admissions"] = protected_admissions
        occ["audits_run"] = alloc.audits_run
        occ["light_audits_run"] = alloc.light_audits_run
        occ["deferred_retries_skipped"] = deferred_retries_skipped
        # page integrity: every injected corruption must be detected at
        # the swap-in gate and quarantined (never scattered to the pool)
        occ["corrupt_pages_injected"] = corrupt_pages_injected
        occ["corrupt_pages_detected"] = corrupt_pages_detected
        occ["quarantined_pages"] = quarantined_pages
        occ["trie_nodes_invalidated"] = trie_nodes_invalidated
        out["page_occupancy"] = occ
    if pcache is not None:
        pstats = pcache.stats()
        pstats["prefill_tokens_total"] = len(latency) * prompt_len
        pstats["cow_copies"] = cow_copies
        pstats["shared_pages_peak"] = alloc.shared_pages_peak
        out["prefix_cache"] = pstats
    if prefix_index is not None:
        out["replica"] = {
            "replica_id": int(replica_id),
            "cross_replica_hits": cross_replica_hits,
            "cross_replica_hit_rate": cross_replica_hits
            / max(len(latency), 1),
            "migrated_pages": migrated_pages,
            "migrated_tokens": migrated_tokens,
            "index_pages_published": index_publishes,
            "index": prefix_index.stats(),
        }
    return out


def serve_replicated(arch: str, *, n_replicas: int = 2,
                     smoke: bool = True, seed: int = 0, cfg=None,
                     options: Optional[ServeOptions] = None,
                     resilience: Optional[ResilienceOptions] = None
                     ) -> Dict[str, Any]:
    """N-replica serve harness around one :class:`SharedPrefixIndex`.

    Each replica owns its own page pool, trie, and decode state
    (replicas run sequentially in-process — the point is the index
    protocol, not wall-clock overlap) and serves the same seeded
    workload: the situation where N frontends all carry one popular
    system prompt.  Replica 0 prefills its prefixes cold and publishes
    them; later replicas migrate those pages instead of re-running the
    shared-prefix prefill — the report aggregates the cross-replica hit
    rate and the prefill tokens the migrations saved.  Every replica's
    outputs are bitwise equal across replicas (same prompts, same
    math — migration only moves pages, never changes what they hold).
    """
    index = SharedPrefixIndex()
    opt = options or ServeOptions()
    reports = []
    for rid in range(int(n_replicas)):
        reports.append(serve(arch, smoke=smoke, seed=seed, cfg=cfg,
                             options=opt, resilience=resilience,
                             prefix_index=index, replica_id=rid))
    hits = sum(r["replica"]["cross_replica_hits"] for r in reports)
    requests = sum(len(r["outputs"]) for r in reports)
    for a, b in zip(reports, reports[1:]):
        assert a["outputs"] == b["outputs"], \
            "replicas serving the same workload must agree bitwise"
    return {
        "replicas": reports,
        "n_replicas": int(n_replicas),
        "requests": requests,
        "cross_replica_hits": hits,
        "cross_replica_hit_rate": hits / max(requests, 1),
        "migrated_pages": sum(r["replica"]["migrated_pages"]
                              for r in reports),
        "migrated_tokens": sum(r["replica"]["migrated_tokens"]
                               for r in reports),
        "prefill_tokens_saved": sum(
            r.get("prefix_cache", {}).get("prefill_tokens_saved", 0)
            for r in reports),
        "outputs_equal": True,
        "index": index.stats(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen3-4b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced same-family config (--no-smoke: the "
                         "published widths)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=1)
    ap.add_argument("--paged", action="store_true",
                    help="serve from the paged KV pool")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="shared-prefix page cache (implies --paged)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="prompts share their first N tokens")
    ap.add_argument("--faults-seed", type=int, default=None,
                    help="inject a seeded FaultPlan schedule "
                         "(implies --paged)")
    ap.add_argument("--max-steps-per-request", type=int, default=None,
                    help="deadline watchdog: retire a slot as timed_out "
                         "after N held steps")
    ap.add_argument("--replicas", type=int, default=0,
                    help="run N serve replicas around one shared prefix "
                         "index (implies --paged --prefix-cache)")
    args = ap.parse_args()
    use_compile_cache()
    cfg = (SMOKE if args.smoke else ARCHS)[args.arch]
    if args.replicas:
        args.paged = args.prefix_cache = True
    if args.paged or args.prefix_cache or args.faults_seed is not None:
        from repro.models.config import KVCacheConfig
        cfg = dataclasses.replace(
            cfg, kv=KVCacheConfig(layout="paged",
                                  prefix_cache=args.prefix_cache))
    faults = None
    if args.faults_seed is not None:
        faults = FaultPlan.seeded(args.faults_seed,
                                  steps=args.requests * args.gen_len,
                                  slots=args.slots)
        print(f"[serve] fault schedule (seed {args.faults_seed}):")
        print(faults.describe())
    opts = ServeOptions(n_requests=args.requests, batch_slots=args.slots,
                        gen_len=args.gen_len, prompt_len=args.prompt_len,
                        shared_prefix_len=args.shared_prefix_len)
    res = ResilienceOptions(
        max_steps_per_request=args.max_steps_per_request)
    if args.replicas:
        rep = serve_replicated(args.arch, n_replicas=args.replicas,
                               smoke=args.smoke, cfg=cfg, options=opts,
                               resilience=res)
        print(f"[serve] {rep['n_replicas']} replicas, "
              f"{rep['requests']} requests: cross-replica hit rate "
              f"{rep['cross_replica_hit_rate']:.2f} "
              f"({rep['cross_replica_hits']} migrations, "
              f"{rep['migrated_pages']} pages / "
              f"{rep['migrated_tokens']} tokens migrated), prefill "
              f"tokens saved {rep['prefill_tokens_saved']}, "
              f"outputs_equal={rep['outputs_equal']}")
        return
    out = serve(args.arch, smoke=args.smoke, cfg=cfg, options=opts,
                faults=faults, resilience=res)
    dev = jax.devices()[0]
    print(f"[serve] generated {out['tokens_generated']} tokens over "
          f"{len(out['outputs'])} requests "
          f"({out['tok_per_s']:.1f} tok/s on {dev.platform} "
          f"{dev.device_kind}, "
          f"mean request latency {out['latency_mean_s'] * 1e3:.1f} ms)")
    sp = out["spans"]

    def secs(name):
        return sp.get(name, {}).get("total_s", 0.0)
    loop = secs("serve.step")
    print(f"[serve] host time: set-up {secs('serve.setup'):.3f} s, "
          f"{out['steps']} loop steps {loop:.3f} s (prefill "
          f"{secs('serve.prefill'):.3f} s, decode steps "
          f"{loop - secs('serve.prefill'):.3f} s; device reads "
          f"{secs('serve.pull'):.3f} s over {out['pulls']} pulls)")
    if "decode_fetch" in out:
        f = out["decode_fetch"]
        print(f"[serve] SATA decode attention-kernel KV fetch: "
              f"{f['kv_fetch_bytes_plan']} B vs "
              f"{f['kv_fetch_bytes_dense']} B dense "
              f"({f['fetch_reduction']:.2f}x kernel-side); with plan "
              f"traffic ({f['plan_fetch_bytes']} B, "
              f"{f['replans']:.0f} re-plans): {f['true_reduction']:.2f}x "
              f"end-to-end")
    if "page_occupancy" in out:
        o = out["page_occupancy"]
        print(f"[serve] paged pool: {o['pages_in_use_peak']}/{o['n_pages']}"
              f" pages peak, HBM used {o['hbm_used_peak_bytes']} B of "
              f"{o['hbm_reserved_bytes']} B reserved "
              f"({o['reserved_vs_contiguous']:.2f}x less reserved than "
              f"contiguous would need; {o['deferred_claims']} deferred "
              f"claims, {o['stalled_steps']} stalled steps)")
        if o["preemptions"] or o["crashes"]:
            print(f"[serve] fault tolerance: {o['host_swaps']} host-swaps "
                  f"({o['tokens_salvaged']} tokens salvaged, "
                  f"{o['swap_restores']} restores, re_prefill_tokens="
                  f"{o['re_prefill_tokens']}, cold_replans="
                  f"{o['swap_cold_replans']}), "
                  f"{o['requeue_preemptions']} requeues "
                  f"({o['requeue_tokens_discarded']} tokens discarded), "
                  f"{o['crashes']} crashes recovered, "
                  f"{o['audits_run']} invariant audits")
    if "prefix_cache" in out:
        p = out["prefix_cache"]
        print(f"[serve] prefix cache: hit-rate {p['hit_rate']:.2f} "
              f"({p['hits']}/{p['requests']}), prefill tokens saved "
              f"{p['prefill_tokens_saved']}/{p['prefill_tokens_total']}, "
              f"{p['cow_copies']} CoW copies, {p['cached_pages']} cached "
              f"pages ({p['evictions']} evicted), shared-page peak "
              f"{p['shared_pages_peak']}")


if __name__ == "__main__":
    main()
