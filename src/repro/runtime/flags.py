"""Process-wide dispatch flags.

The paper's recipe is explicitly "out-of-the-box" (no custom kernels) — that
remains the reference configuration (``kernels/ref.py`` oracles).  The Pallas
kernels are the beyond-paper optimization layer; now that flash attention is
differentiable (fused backward kernels, see ``kernels/flash_attention.py``)
it is ON by default on accelerator backends: ``REPRO_FLASH_ATTENTION=auto``
enables the tiled path whenever the backend is not CPU and the shapes divide
the block sizes (``kernels.ops.flash_supported``), with a clean fallback to
the reference path otherwise.  On CPU the Pallas interpreter would be a
slowdown, not a speedup, so ``auto`` resolves to off there; ``=1`` forces the
kernel, ``=0`` forces it off.  Whether a kernel runs compiled or interpreted
is not a flag: the backend decides (``kernels.ops``), CPU means interpret.
"""

from __future__ import annotations

import os
from contextlib import contextmanager

_FLAGS = {
    "flash_attention": os.environ.get("REPRO_FLASH_ATTENTION", "auto"),
    "flash_decode": os.environ.get("REPRO_FLASH_DECODE", "0") == "1",
    "fused_rmsnorm": os.environ.get("REPRO_FUSED_RMSNORM", "0") == "1",
    # flash block-size overrides (autotuning hook): None → heuristic in
    # kernels.ops; threaded down from ParallelismConfig.flash_bq/flash_bk
    # by the step factories in core.stepfn.
    "flash_block_q": None,
    "flash_block_k": None,
}


def use_flash_attention() -> bool:
    v = _FLAGS["flash_attention"]
    if isinstance(v, bool):
        return v
    if v == "auto":
        import jax
        return jax.default_backend() != "cpu"
    return v == "1"


def use_flash_decode() -> bool:
    return bool(_FLAGS["flash_decode"])


def use_fused_rmsnorm() -> bool:
    return bool(_FLAGS["fused_rmsnorm"])


def flash_block_sizes():
    """(bq, bk) overrides for the flash kernels; None entries → heuristic."""
    return _FLAGS["flash_block_q"], _FLAGS["flash_block_k"]


def set_flag(name: str, value) -> None:
    if name not in _FLAGS:
        raise KeyError(name)
    _FLAGS[name] = value


@contextmanager
def flag_ctx(**kv):
    old = {k: _FLAGS[k] for k in kv}   # KeyError on unknown flag names
    _FLAGS.update(kv)
    try:
        yield
    finally:
        _FLAGS.update(old)
