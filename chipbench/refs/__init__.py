"""Reference modules: each computes one family of models in float32,
its controls and its FLOP count.  A configuration names its own by path
(``"reference": "refs/dense.py"``); the interface is in ``dense.py``."""
