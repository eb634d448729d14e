"""Build-provenance stamp shared by benchmarks and the flight recorder.

One dict answers "which commit/backend produced this artifact?": git SHA,
jax version, the device JAX computes on (``device``: platform,
``device_kind`` and count, as ``jax.devices()`` reports them), the host
OS, and the two choices that change the numbers (the batch simulator
``qn_impl`` and ``REPRO_SHARD``).  Lives in ``obs`` (not
``benchmarks/``) so library code — recorder dumps, the ``/statz``
endpoint — can stamp artifacts without importing the benchmark harness;
``benchmarks/common.provenance()`` is now a re-export of this.

Every field degrades to ``None`` rather than failing: stamps must work
outside a git checkout and without jax just the same.  Computed once per
process (the SHA cannot change under a running solver).
"""
from __future__ import annotations

import os
import platform as _platform
import subprocess
from typing import Optional

_PROVENANCE: Optional[dict] = None


def provenance() -> dict:
    global _PROVENANCE
    if _PROVENANCE is not None:
        return _PROVENANCE
    sha = None
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or None
    except Exception:
        pass
    jax_version = None
    devices = None
    device = None
    qn_impl = None
    try:
        import jax
        jax_version = jax.__version__
        devs = jax.devices()
        devices = len(devs)
        device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": devices}
        from repro.core import qn_sim
        qn_impl = qn_sim.default_impl()
    except Exception:
        pass
    shard = None
    try:
        from repro.core import partition
        shard = partition.shard_info()      # spec + device count + mesh
    except Exception:
        pass
    _PROVENANCE = {
        "git_sha": sha,
        "jax": jax_version,
        "platform": _platform.platform(),
        "python": _platform.python_version(),
        "qn_impl": qn_impl,
        "devices": devices,
        "device": device,
        "repro_shard": os.environ.get("REPRO_SHARD", "auto"),
        "shard": shard,
    }
    return _PROVENANCE
