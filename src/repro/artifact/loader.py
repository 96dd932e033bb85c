"""`load(path) -> LoadedProgram` — execute a ``.cutie`` artifact, no graph.

The loader reconstructs exactly what the container holds — `ProgramInfo`
metadata, the compiled `ExecutionPlan`, and the trit-packed `WeightMemory`
images — and wraps them in a `LoadedProgram`.  Its execution surface
(``forward``/``spatial_forward``/``temporal_forward`` on every backend,
``stream()`` sessions, ``serve()`` pools) is `api.program.PlanProgram`'s,
the same code a `DeployedProgram` runs: the plan is the program.  There is
NO `CutieGraph` (or any Python graph object) on this path: serving reads
its metadata from `ProgramInfo`, and each layer's kernel block comes from
the artifact's own plan geometry (`kernels.autotune`), so an artifact runs
the same launches as the `DeployedProgram` it was saved from.
"""
from __future__ import annotations

import os
from typing import Union

from repro.api.program import PlanProgram, silicon_report_from_plan
from repro.artifact.format import ProgramInfo, assemble_parts, parse


class LoadedProgram(PlanProgram):
    """An executable program reconstructed from a ``.cutie`` artifact."""

    def __init__(self, info: ProgramInfo, plan, memory):
        self.info = info
        self.plan = plan
        self.memory = memory

    @property
    def graph(self) -> ProgramInfo:
        """Serving metadata (`ProgramInfo`) under the attribute name the
        serving stack reads — NOT a `CutieGraph`."""
        return self.info

    # -- silicon model -----------------------------------------------------

    def silicon_report(self, v: float = 0.5, hw=None, source: str = "sim"):
        """Cycles/energy of THIS artifact.  Defaults to ``source="sim"``:
        the stall-aware counters walk the loaded plan and the sparsity of
        the loaded weight images prices the dynamic energy — the golden
        model runs on what the device would actually execute, not on an
        ideal re-derivation.  Calibration uses the paper corner carried in
        the artifact header (when present)."""
        return silicon_report_from_plan(
            self.plan, v=v, hw=hw, source=source, memory=self.memory,
            paper_energy_uj=self.info.paper_energy_uj,
            paper_inf_per_s=self.info.paper_inf_per_s,
        )

    # -- round trip --------------------------------------------------------

    def to_bytes(self) -> bytes:
        """Re-assemble — byte-identical to the artifact this was loaded
        from (the loader is lossless; pinned in tests/test_artifact.py)."""
        return assemble_parts(self.info, self.plan, self.memory)


def loads(data: bytes) -> LoadedProgram:
    """``.cutie`` bytes -> `LoadedProgram` (raises `ArtifactError` and its
    typed subclasses on malformed input — never a garbage decode)."""
    info, plan, memory = parse(data)
    return LoadedProgram(info, plan, memory)


def load(path: Union[str, os.PathLike]) -> LoadedProgram:
    """Read a ``.cutie`` file and return its executable `LoadedProgram`."""
    with open(path, "rb") as f:
        return loads(f.read())


def save(program, path: Union[str, os.PathLike]) -> int:
    """Assemble ``program`` (a `DeployedProgram` or `LoadedProgram`) and
    write it to ``path``; returns the byte count."""
    from repro.artifact.format import assemble

    data = assemble(program)
    with open(path, "wb") as f:
        f.write(data)
    return len(data)
