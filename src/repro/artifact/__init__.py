"""repro.artifact — self-contained binary program artifacts (``.cutie``).

The deployment container the paper's SoC story implies: compiled
`ExecutionPlan` + trit-packed weight-memory images + folded scale/threshold
tables in one versioned, CRC-checked byte string.

    from repro.artifact import assemble, load, disassemble, reassemble

    data   = assemble(deployed)          # DeployedProgram -> .cutie bytes
    prog   = load("net.cutie")           # -> LoadedProgram (no CutieGraph)
    logits = prog.forward(x)             # backend="fused" | "ref" | "bitsim"
    pool   = prog.serve(pool_size=8)     # fleet serving from the artifact
    text   = disassemble(data)           # readable listing
    assert reassemble(text) == data      # lossless round trip

CLI: ``python -m repro.artifact {build,dis,asm,info,verify}``.
Format spec and versioning policy: docs/artifact.md.
"""
from repro.artifact.format import (
    ArtifactError,
    BadMagicError,
    CRCMismatchError,
    ProgramInfo,
    TruncatedArtifactError,
    UnsupportedVersionError,
    VERSION,
    assemble,
    assemble_parts,
    canonical_json,
    parse,
)
from repro.artifact.listing import disassemble, reassemble
from repro.artifact.loader import LoadedProgram, load, loads, save

__all__ = [
    "ArtifactError",
    "BadMagicError",
    "CRCMismatchError",
    "ProgramInfo",
    "TruncatedArtifactError",
    "UnsupportedVersionError",
    "VERSION",
    "assemble",
    "assemble_parts",
    "canonical_json",
    "parse",
    "disassemble",
    "reassemble",
    "LoadedProgram",
    "load",
    "loads",
    "save",
]
