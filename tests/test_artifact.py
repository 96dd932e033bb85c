"""`repro.artifact` format contracts: the ``.cutie`` container itself.

Pinned here:

  * every malformation raises its own typed `ArtifactError` subclass —
    truncation, bad magic, unknown version, CRC mismatch — never a garbage
    decode;
  * assembly is **deterministic**: the same program yields byte-identical
    artifacts in the same process, across processes, and (via a hand-built
    weight memory with no PRNG anywhere) across library versions — a sha256
    is pinned;
  * the loader is lossless (``loads(data).to_bytes() == data``) and the
    disassembler round-trips byte-identically (``reassemble(disassemble(
    data)) == data``);
  * the ``python -m repro.artifact`` CLI (build/dis/asm/info/verify) works
    end to end and its gates actually gate.

Execution equivalence (loaded artifact vs the in-memory `DeployedProgram`
on every backend) lives in tests/test_artifact_loader.py.
"""
import hashlib
import json
import os
import struct
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, artifact
from repro.api.program import CutieProgram
from repro.artifact import (
    ArtifactError,
    BadMagicError,
    CRCMismatchError,
    ProgramInfo,
    TruncatedArtifactError,
    UnsupportedVersionError,
)
from repro.artifact.format import HEADER, MAGIC, VERSION, assemble_parts, canonical_json
from repro.core.ternary import pack_ternary
from repro.sim.memory import LayerImage, WeightMemory
from repro.sim.plan import lower

REPO_ROOT = Path(__file__).resolve().parents[1]


def _deployed(name="cifar10_tnn_smoke", seed=0, calib_seed=None, **init_kw):
    prog = CutieProgram(api.get_graph(name))
    params = prog.init(jax.random.PRNGKey(seed), **init_kw)
    calib = None
    if calib_seed is not None:
        g = prog.graph
        shape = ((1, 3, *g.input_hw, g.input_ch) if g.is_temporal
                 else (1, *g.input_hw, g.input_ch))
        calib = jnp.sign(jax.random.normal(jax.random.PRNGKey(calib_seed), shape))
    return prog.quantize(params, calib=calib)


@pytest.fixture(scope="module")
def smoke_bytes():
    return artifact.assemble(_deployed(calib_seed=7))


# ---------------------------------------------------------------------------
# Typed load-path errors — one distinct class per malformation
# ---------------------------------------------------------------------------

class TestErrors:
    def test_truncated_header(self):
        with pytest.raises(TruncatedArtifactError, match="header alone"):
            artifact.loads(MAGIC[:4])

    def test_truncated_payload(self, smoke_bytes):
        with pytest.raises(TruncatedArtifactError, match="payload truncated"):
            artifact.loads(smoke_bytes[:-3])

    def test_bad_magic(self, smoke_bytes):
        with pytest.raises(BadMagicError, match="bad magic"):
            artifact.loads(b"NOTCUTIE" + smoke_bytes[8:])

    def test_unsupported_version(self, smoke_bytes):
        # bump the u16 at offset 8; the CRC covers only the payload, so the
        # version check (not the CRC) must be what rejects this
        data = smoke_bytes[:8] + struct.pack("<H", VERSION + 1) + smoke_bytes[10:]
        with pytest.raises(UnsupportedVersionError, match="this reader understands"):
            artifact.loads(data)

    def test_v1_payload_still_loads(self, smoke_bytes):
        """The MIN_VERSION contract: a v1 artifact (pre-stride PLAN
        schema) loads on the v2 reader with every stride defaulting to 1."""
        listing = artifact.disassemble(smoke_bytes)
        lines = []
        for ln in listing.splitlines():
            if ln.strip().startswith("version"):
                lines.append("version 1")
            elif ln.strip().startswith("json") and '"stride"' in ln:
                pad, body = ln.split("json ", 1)
                obj = json.loads(body)
                for lp in obj.get("layers", ()):
                    lp.pop("stride", None)
                lines.append(pad + "json " + canonical_json(obj).decode())
            else:
                lines.append(ln)
        v1 = artifact.reassemble("\n".join(lines))
        assert v1 != smoke_bytes  # genuinely the old schema
        loaded = artifact.loads(v1)
        assert all(lp.stride == 1 for lp in loaded.plan.layers)

    def test_crc_mismatch(self, smoke_bytes):
        flipped = smoke_bytes[-1] ^ 0xFF
        with pytest.raises(CRCMismatchError, match="CRC-32"):
            artifact.loads(smoke_bytes[:-1] + bytes([flipped]))

    def test_missing_sections(self):
        import zlib

        empty = HEADER.pack(MAGIC, VERSION, 0, 0, zlib.crc32(b"") & 0xFFFFFFFF)
        with pytest.raises(ArtifactError, match="missing its META or PLAN"):
            artifact.loads(empty)

    def test_errors_are_catchable_as_artifact_and_value_errors(self):
        for cls in (TruncatedArtifactError, BadMagicError,
                    UnsupportedVersionError, CRCMismatchError):
            assert issubclass(cls, ArtifactError)
            assert issubclass(cls, ValueError)

    def test_not_a_file_of_ours(self):
        # a plausible-looking foreign binary must fail on magic, nothing else
        with pytest.raises(BadMagicError):
            artifact.loads(b"\x7fELF" + b"\x00" * 64)


# ---------------------------------------------------------------------------
# Round trips: loader lossless, disassembler byte-identical
# ---------------------------------------------------------------------------

class TestRoundTrip:
    def test_loader_is_lossless(self, smoke_bytes):
        loaded = artifact.loads(smoke_bytes)
        assert loaded.to_bytes() == smoke_bytes
        # assemble() dispatches on the loaded program too
        assert artifact.assemble(loaded) == smoke_bytes

    def test_dis_asm_byte_identity(self, smoke_bytes):
        listing = artifact.disassemble(smoke_bytes)
        assert "section META" in listing and "section PLAN" in listing
        assert artifact.reassemble(listing) == smoke_bytes

    def test_tables_survive_verbatim(self, smoke_bytes):
        """The packed weight bytes in the artifact are the quantizer's
        bytes, untouched — `api.quantize` stays the single pack path."""
        dep = _deployed(calib_seed=7)
        plan = lower(dep.graph)
        want = WeightMemory.from_tables(plan, dep.tables, dep.graph.act_threshold)
        got = artifact.loads(smoke_bytes).memory
        assert len(got.images) == len(want.images)
        for a, b in zip(got.images, want.images):
            assert (a.kind, a.index, a.dilation) == (b.kind, b.index, b.dilation)
            np.testing.assert_array_equal(a.packed, b.packed)
            np.testing.assert_array_equal(a.eff_scale, b.eff_scale)
            np.testing.assert_array_equal(np.asarray(a.threshold),
                                          np.asarray(b.threshold))

    def test_per_channel_threshold_vector_round_trips(self):
        dep = _deployed("dvs_cnn_tcn_smoke", calib_seed=3,
                        learn_thresholds="per_channel")
        data = artifact.assemble(dep)
        loaded = artifact.loads(data)
        vec_images = [i for i in loaded.memory.images
                      if np.ndim(i.threshold) == 1]
        assert vec_images, "per-channel thresholds should survive as vectors"
        assert loaded.to_bytes() == data
        assert artifact.reassemble(artifact.disassemble(data)) == data

    def test_program_info_ignores_unknown_keys(self, smoke_bytes):
        info = artifact.loads(smoke_bytes).info
        d = dict(info.to_dict(), future_field="from a newer writer")
        assert ProgramInfo.from_dict(d) == info


# ---------------------------------------------------------------------------
# Determinism — the byte-stability contract
# ---------------------------------------------------------------------------

# sha256 of the hand-built cifar10_tnn_smoke artifact below: no PRNG, no
# library-version-dependent float anywhere — trits are (arange % 3) - 1 and
# scales are small-integer/8 (exact in float32).  If this pin moves, the
# on-disk format changed: bump VERSION and docs/artifact.md.
# Pin history: v1 7b1673af...390c; v2 (PLAN layers carry "stride")
# d0116d48...7199; v3 (PLAN layers carry "shortcut"):
_HAND_BUILT_SHA256 = (
    "67d5c8906d2fa496c8ccdbc40f609e1ecccdd9123d1d005e29ea47482d103b8e"
)


def _hand_built_parts():
    g = api.get_graph("cifar10_tnn_smoke")
    plan = lower(g)
    images = []
    for lp in plan.weight_layers():
        if lp.kind == "fc":
            k = lp.c_in
            t = ((np.arange(k * lp.c_out, dtype=np.int64) % 3) - 1
                 ).reshape(k, lp.c_out)
            t_pad = np.pad(t.astype(np.int8), ((0, (-k) % 4), (0, 0)))
            packed = np.asarray(pack_ternary(t_pad, axis=0), np.uint8)
            scale = ((np.arange(lp.c_out) + 1) / 8.0).astype(np.float32)
            images.append(LayerImage(kind="fc", index=lp.index, packed=packed,
                                     eff_scale=scale, threshold=0.0))
        else:
            shape = (lp.kh, lp.kw, lp.c_pad, lp.c_out)
            trits = ((np.arange(int(np.prod(shape)), dtype=np.int64) % 3) - 1
                     ).reshape(shape).astype(np.int8)
            packed = np.asarray(pack_ternary(trits, axis=2), np.uint8)
            scale = ((np.arange(lp.c_out) + 1) / 8.0).astype(np.float32)
            images.append(LayerImage(kind=lp.kind, index=lp.index, packed=packed,
                                     eff_scale=scale, threshold=0.5, dilation=1))
    fc = next((i.eff_scale for i in images if i.kind == "fc"), None)
    return ProgramInfo.from_graph(g), plan, WeightMemory(images=images, fc_scale=fc)


class TestDeterminism:
    def test_hand_built_sha256_pin(self):
        data = assemble_parts(*_hand_built_parts())
        assert hashlib.sha256(data).hexdigest() == _HAND_BUILT_SHA256

    def test_hand_built_artifact_executes(self):
        """The pinned artifact is not a fixture blob — it loads and runs."""
        loaded = artifact.loads(assemble_parts(*_hand_built_parts()))
        x = jnp.sign(jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16, 3)))
        got = loaded.forward(x, backend="bitsim")
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(loaded.forward(x, backend="ref")))

    def test_same_process_reassembly_is_stable(self):
        """Quantizing the same params twice yields the same bytes — no
        dict-ordering or id()-dependent state leaks into the container."""
        a = artifact.assemble(_deployed(calib_seed=7))
        b = artifact.assemble(_deployed(calib_seed=7))
        assert a == b

    def test_cross_process_assembly_is_stable(self, smoke_bytes):
        """A fresh interpreter assembling the same program must produce the
        same sha256 — sorted JSON keys + fixed endianness, no per-process
        hash randomization anywhere in the byte stream."""
        code = (
            "import hashlib, sys, jax, jax.numpy as jnp\n"
            "from repro import api, artifact\n"
            "from repro.api.program import CutieProgram\n"
            "prog = CutieProgram(api.get_graph('cifar10_tnn_smoke'))\n"
            "params = prog.init(jax.random.PRNGKey(0))\n"
            "calib = jnp.sign(jax.random.normal(jax.random.PRNGKey(7), (1, 16, 16, 3)))\n"
            "dep = prog.quantize(params, calib=calib)\n"
            "sys.stdout.write(hashlib.sha256(artifact.assemble(dep)).hexdigest())\n"
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = str(REPO_ROOT / "src") + os.pathsep + env.get(
            "PYTHONPATH", "")
        out = subprocess.run(
            [sys.executable, "-c", code], env=env, capture_output=True,
            text=True, check=True,
        )
        assert out.stdout.strip() == hashlib.sha256(smoke_bytes).hexdigest()

    def test_canonical_json_rejects_nan(self):
        with pytest.raises(ValueError):
            canonical_json({"x": float("nan")})

    def test_canonical_json_sorts_keys(self):
        assert canonical_json({"b": 1, "a": 2}) == b'{"a":2,"b":1}'


# ---------------------------------------------------------------------------
# The CLI: python -m repro.artifact {build,dis,asm,info,verify}
# ---------------------------------------------------------------------------

class TestCLI:
    def test_build_dis_asm_info_verify(self, tmp_path, capsys):
        from repro.artifact.__main__ import main

        art = tmp_path / "net.cutie"
        lst = tmp_path / "net.lst"
        art2 = tmp_path / "net2.cutie"
        assert main(["build", "cifar10_tnn_smoke", "-o", str(art),
                     "--no-calib"]) == 0
        assert art.stat().st_size > HEADER.size
        assert main(["dis", str(art), "-o", str(lst)]) == 0
        assert "section META" in lst.read_text()
        # the --expect gate: reassembly must be byte-identical to the source
        assert main(["asm", str(lst), "-o", str(art2),
                     "--expect", str(art)]) == 0
        assert art2.read_bytes() == art.read_bytes()
        assert main(["info", str(art)]) == 0
        out = capsys.readouterr().out
        assert "cifar10_tnn_smoke" in out and "weight images" in out
        assert main(["verify", str(art)]) == 0
        assert "round trip lossless" in capsys.readouterr().out

    def test_asm_expect_gate_fails_on_mismatch(self, tmp_path, capsys):
        from repro.artifact.__main__ import main

        a = tmp_path / "a.cutie"
        b = tmp_path / "b.cutie"
        lst = tmp_path / "a.lst"
        out = tmp_path / "out.cutie"
        assert main(["build", "cifar10_tnn_smoke", "-o", str(a),
                     "--no-calib"]) == 0
        assert main(["build", "cifar10_tnn_smoke", "-o", str(b),
                     "--no-calib", "--seed", "1"]) == 0
        assert main(["dis", str(a), "-o", str(lst)]) == 0
        assert main(["asm", str(lst), "-o", str(out),
                     "--expect", str(b)]) == 1
        assert "FAIL" in capsys.readouterr().err

    def test_verify_temporal_program(self, tmp_path, capsys):
        from repro.artifact.__main__ import main

        art = tmp_path / "dvs.cutie"
        assert main(["build", "dvs_cnn_tcn_smoke", "-o", str(art),
                     "--no-calib"]) == 0
        assert main(["verify", str(art), "--frames", "3"]) == 0
        assert "bit-exact" in capsys.readouterr().out
