"""A traced window by the names of a model with latent attention,
hyper-connection streams and a prediction module: the scopes ``attn.latent``
(inside ``attn``), ``hc.gates`` and ``hc.mix`` (inside ``layers``, beside
``attn`` and ``mlp``) and ``mtp`` of ``multiverso_tpu/models/transformer.py``,
and the three two-width flash kernels ``flash_mla_fwd``, ``flash_mla_bwd_dq``
and ``flash_mla_bwd_dkv`` of ``ops/flash_attention.py``.

``program.SCOPES`` / ``program.KERNELS`` and ``kinds.SCOPES`` /
``kinds.KERNELS`` are constants that hold none of these names, so the
readers that need them share this walk of the run's trace (a fifth one; to
be folded into ``program.py`` by a ``benchmark`` PR, ``PERF.md`` section 7).
An instruction is booked to the innermost of ``SCOPES`` in its ``op_name``,
whatever the phase, and a Mosaic custom call to the innermost of
``KERNELS``.  ``mtp`` is booked apart: everything with that scope anywhere
in its path, the module's own block, head and loss included (so its
``attn.latent`` and ``hc.*`` time is in both).  The roofline shares are
computed from the facts the runner ``lm_train_latent`` gives
(``benchmarks/flops_xing.py``).

A program without any of this (the parent of the PR that added it, a model
without these layers) gives ``None`` and the readers leave their metric
out.
"""

from __future__ import annotations

import functools
import glob
import os
from dataclasses import dataclass
from typing import Dict, Optional

from benchmarks.trace import program
from benchmarks.trace.reduce import (WINDOW_SPAN, _clip, classify,
                                     load_xplane, self_times)

__all__ = ["SCOPES", "KERNELS", "MODULE", "Latent", "summarize",
           "of_reading", "scope_ms_per_step", "module_ms_per_step",
           "kernel_roofline"]

SCOPES = ("attn.latent", "hc.gates", "hc.mix")
KERNELS = ("flash_mla_fwd", "flash_mla_bwd_dq", "flash_mla_bwd_dkv")
MODULE = "mtp"
# the runner's facts hold a kernel's FLOPs and least bytes under these
_KERNEL_PART = {"flash_mla_fwd": "fwd"}


@dataclass
class Latent:
    """Seconds of device self time in the window, means over the chips."""
    step_programs: int
    busy_s: float
    by_scope_s: Dict[str, float]
    by_kernel_s: Dict[str, float]
    module_s: float


def summarize(trace, index) -> Optional[Latent]:
    windows = [e for e in trace.host if e.name == WINDOW_SPAN]
    if not trace.devices or not windows:
        return None
    t0 = min(w.start for w in windows)
    t1 = max(w.end for w in windows)
    chips = len(trace.devices)
    scopes = {s: 0.0 for s in SCOPES}
    kernels = {k: 0.0 for k in KERNELS}
    busy = module = 0.0
    programs = 0
    for dev in trace.devices.values():
        for e, self_ns in self_times(_clip(dev.ops, t0, t1)):
            busy += self_ns
            op_name = index.op_name(e.name)
            where = program.scope(op_name, among=SCOPES)
            if where is not None:
                scopes[where] += self_ns
            if any(name == MODULE
                   for name, _ in program.components(op_name or "")):
                module += self_ns
            if classify(e.name) == "mosaic":
                which = program.scope(op_name, among=KERNELS)
                if which is not None:
                    kernels[which] += self_ns
        programs += sum(1 for e in _clip(dev.modules, t0, t1)
                        if e.name.startswith("jit_step"))
    if not any(scopes.values()) and not any(kernels.values()) and not module:
        return None
    return Latent(step_programs=programs // chips,
                  busy_s=busy / chips / 1e9,
                  by_scope_s={k: v / chips / 1e9 for k, v in scopes.items()},
                  by_kernel_s={k: v / chips / 1e9
                               for k, v in kernels.items()},
                  module_s=module / chips / 1e9)


@functools.lru_cache(maxsize=1)
def _of_file(path: str, mtime: float) -> Optional[Latent]:
    return summarize(load_xplane(path), program.ScopeIndex.from_xplane(path))


def of_reading(reading) -> Optional[Latent]:
    """The ``Latent`` of the run a reader is reading: the newest trace under
    ``.bench_out/trace/`` is this run's (``program.of_reading``)."""
    if reading.trace is None:
        return None
    from benchmarks.harness import REPO

    found = glob.glob(os.path.join(REPO, ".bench_out", "trace", "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    return _of_file(path, os.path.getmtime(path))


# ------------------------------------------------- one call for each reader
def _per_step(found: Optional[Latent], seconds: float) -> Optional[float]:
    if found is None or found.step_programs <= 0 or seconds <= 0:
        return None
    return 1e3 * seconds / found.step_programs


def scope_ms_per_step(reading, *names: str) -> Optional[float]:
    """Device self time a step whose innermost scope of ``SCOPES`` is one of
    ``names``, any phase, kernels included, ms."""
    found = of_reading(reading)
    return _per_step(found, sum(found.by_scope_s[n] for n in names)
                     if found else 0.0)


def module_ms_per_step(reading) -> Optional[float]:
    """Device self time a step anywhere under the scope ``mtp``, ms."""
    found = of_reading(reading)
    return _per_step(found, found.module_s if found else 0.0)


def kernel_roofline(reading, name: str) -> Optional[float]:
    """The forward kernel's share of its roofline, percent: what the pass
    requires a step (``flops_xing.mla_kernel_flops``: the scores at 192, the
    values at 128) at the bf16 peak, or its least bytes at the HBM peak, the
    larger, over the time in the Mosaic call of that name.  The backward is
    read by family (``program.family_roofline``, PR 39); this walk still
    books the split kernels' time under their own names."""
    found = of_reading(reading)
    if found is None or not reading.peaks:
        return None
    part = _KERNEL_PART[name]
    f = reading.facts
    work = f.get("mla_kernel_flops_per_step", {}).get(part)
    moved = f.get("mla_kernel_bytes_per_step", {}).get(part)
    return program.roofline_pct(reading, found.by_kernel_s[name], work,
                                moved, found.step_programs)
