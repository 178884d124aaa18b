"""Kept for ``tests/test_deepseek_v32.py``, which imports ``scaled_bias`` from
here and which a benchmark PR may not edit: since PR 38 the
`deepseek_v32_ep32` configuration runs on ``systems/lm.py`` (``"system":
"lm"``), and the selection bias's product by 0.04 is ``lib/weights_lm.py``'s
rule for the leaf's name. A PR outside the benchmark drops the call, and the
next benchmark PR deletes this file (PERF.md §7)."""


def scaled_bias(params, made=None):
    """The tree as ``lib/weights_lm.make_weights`` made it: its selection bias
    holds the product by 0.04 already, so nothing is left to scale."""
    return params
