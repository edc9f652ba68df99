"""Profiler sessions with the program's spans, read back by scope.

``--profile DIR`` on ``repro.launch.mine`` / ``repro.launch.serve`` runs
one JAX profiler session (``jax.profiler.trace``) around the work.  The
program's ``repro.obs.trace`` spans are annotated into it
(``jax.profiler.TraceAnnotation``), so ``miner.run``,
``executor.replay``, ``executor.wait`` and the rest sit on the host
plane on the same clock as the device's ops; open ``DIR`` in
TensorBoard's profile plugin or xprof.

A device op in the trace carries its HLO instruction name, not its
scope.  :func:`scope_times` names each op of an executor program by the
``level{L}/<phase>`` scope of ``Miner.op_scopes()`` (written beside the
trace as ``op_scopes.json``) and sums the device self time by scope.
"""
from __future__ import annotations

# repro: host-module
# Profiler set-up and trace reading; nothing here is traced.

import bisect
import json
from collections import defaultdict
from pathlib import Path

from repro.core.plan import level_phase

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def save_op_scopes(directory: str, op_scopes: dict) -> str:
    path = Path(directory) / "op_scopes.json"
    path.write_text(json.dumps(op_scopes))
    return str(path)


def _instruction(hlo: str) -> str:
    """``%fusion.7 = s32[65536]{0} fusion(...)`` -> ``fusion.7``."""
    return hlo.partition(" = ")[0].strip().lstrip("%")


def scope_times(pd, op_scopes: dict[str, dict[str, str]]
                ) -> dict[str, float]:
    """Device self seconds by ``level{L}/<phase>`` scope, first chip.

    Each op of the ``XLA Ops`` line is matched to the program that ran
    it by the ``XLA Modules`` interval holding its start, then to its
    scope by instruction name; ops of the executor programs outside any
    phase count as ``unscoped``, ops of other programs as ``other``.  A
    ``while`` op's event encloses its body's, so each op counts its
    duration less that of the ops nested in it.
    """
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                break
    else:
        return {}
    modules = sorted((int(e.start_ns), int(e.end_ns), e.name.split("(")[0])
                     for e in lines[MODULES_LINE].events) \
        if MODULES_LINE in lines else []
    starts = [s for s, _, _ in modules]
    out: dict[str, float] = defaultdict(float)
    stack: list[tuple[str, int]] = []              # (scope, end)
    for name, s, e in sorted(((ev.name, int(ev.start_ns), int(ev.end_ns))
                              for ev in lines[OPS_LINE].events),
                             key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        i = bisect.bisect_right(starts, s) - 1
        scopes = (op_scopes.get(modules[i][2])
                  if i >= 0 and s < modules[i][1] else None)
        if scopes is None:
            scope = "other"
        else:
            scope = level_phase(scopes.get(_instruction(name), "")) \
                or "unscoped"
        out[scope] += (e - s) / 1e9
        if stack:
            out[stack[-1][0]] -= (min(e, stack[-1][1]) - s) / 1e9
        stack.append((scope, e))
    return dict(out)


def read(directory: str) -> dict[str, float]:
    """:func:`scope_times` of the newest trace under ``directory``, with
    the ``op_scopes.json`` written beside it."""
    from jax.profiler import ProfileData

    found = sorted(Path(directory).rglob("*.xplane.pb"))
    path = Path(directory) / "op_scopes.json"
    if not found or not path.exists():
        return {}
    return scope_times(ProfileData.from_file(str(found[-1])),
                       json.loads(path.read_text()))
