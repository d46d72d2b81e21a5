"""Percent of the decode gather kernel's roofline: the least time its
calls could take on this chip over their summed device time.

Device time: the ``sata_decode_attention*`` operations wholly inside
the traced window.  Least time of a call: the larger of its FLOPs over
peak FLOP/s and its bytes over peak bandwidth (``flops.py``), for the
planned tiles an average call fetches, which is the window's planned
tiles (``serve()``'s ``kv_fetch_tiles_plan``) over its kernel calls
(decode steps times layers).  The traced stretch and the whole window
see nearly the same context lengths, which this average assumes."""
import flops
import xtrace


def read(run):
    if run.trace is None:
        return None
    n, secs = xtrace.kernel_time(run.trace)
    f = [j.report.get("decode_fetch") for j in run.jobs]
    if n == 0 or secs <= 0 or not f or not all(f):
        return None
    calls = sum(j.report["steps"] for j in run.jobs) * run.model["n_layers"]
    tiles = sum(x["kv_fetch_tiles_plan"] for x in f) / calls
    s = run.shape
    fl, nb = flops.decode_kernel_call(
        run.model, slots=s.slots, tiles=tiles, block=s.page,
        plan_blocks=s.max_len // s.page)
    least = max(fl / run.peak["bf16_flops_per_s"],
                nb / run.peak["hbm_bytes_per_s"])
    return 100.0 * least * n / secs
