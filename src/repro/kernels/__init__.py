"""Pallas kernels: ``qn_event`` and ``amva`` (the planner's accurate and
fast tiers) and ``flash_attention``/``ssd_scan`` (the model stack).

``interpret_mode()`` is the one place that decides how a kernel runs, from
the platform JAX computes on: the CPU interprets every kernel (the tier-1
test path, bit-exact against the ``ref.py`` oracles), the TPU compiles it,
and any other platform has no kernel path and fails loudly.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True on the CPU, False on the TPU; any other platform raises."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path for platform {platform!r}: "
                       f"kernels run interpreted on 'cpu' or compiled on "
                       f"'tpu'")
