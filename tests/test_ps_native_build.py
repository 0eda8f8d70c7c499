"""What the servers' build makes of their float32 loops.

``ps/native/kv_loops.h`` holds the loops; ``ps/native/Makefile`` asks the
release build to pack them and forbids it to fuse them.  The first is
speed (a scalar ``divss`` a weight was 0.7 ms of every lock-step
release), the second is the oracle-pinned trajectory: a ``vfnmadd`` for
``w - lr * g`` rounds once where the reference rounds twice.  These read
the disassembly of the binary the tests and the cells run, so a flag
that drifts (an ``-march``, a ``target_clones`` without
``-ffp-contract=off``, a cost model that goes back to scalar) fails here
and not as an ulp somewhere or a millisecond on the chip.
``tests/test_ps_apply_bits.py`` holds the same binary's results to
NumPy's.

The FTRL-Proximal step of a keyed push (``FtrlStepPacked``, PR 54) is
spelled in SSE2 intrinsics, not left to the vectoriser: the release
build's holds the packed square root and divide, the sanitizer builds'
(``-DDISTLR_SCALAR_LOOPS`` on the ``SANFLAGS`` line) hold the scalar
ones, and neither may fuse.
"""

from __future__ import annotations

import os
import platform
import re
import shutil
import subprocess

import pytest

from distlr_tpu.ps.build import build_native, native_dir, server_binary

pytestmark = pytest.mark.skipif(
    shutil.which("make") is None or shutil.which("g++") is None,
    reason="no native toolchain",
)

#: loop -> the packed SSE/AVX instructions its vectorised body must hold
LOOPS = {
    "SgdStepPacked": ("mulps", "subps"),
    "MergeAddPacked": ("addps",),
    "MeanStepPacked": ("mulps", "divps", "subps"),
    # four coordinates of a keyed push under FTRL-Proximal
    "FtrlStepPacked": ("sqrtps", "divps", "mulps", "addps", "subps"),
}
PACKED = {"mulps", "divps", "subps", "addps", "sqrtps"}
FUSED = re.compile(r"\bvf(n?m(add|sub)|maddsub|msubadd)\d*[ps][sd]\b")


def _makefile_lines() -> list[str]:
    with open(os.path.join(native_dir(), "Makefile")) as f:
        return f.read().splitlines()


def _flags(var: str) -> list[str]:
    (line,) = [ln for ln in _makefile_lines()
               if re.match(rf"{var}\s*\??=", ln)]
    return line.split("=", 1)[1].split()


def _disassembly(variant: str) -> dict[str, list[str]]:
    """``distlr::loops::<name>`` -> its instructions (mnemonic and
    operands), out of ``objdump -d`` of the ``variant`` server."""
    if shutil.which("objdump") is None:
        pytest.skip("no objdump here")
    if platform.machine() not in ("x86_64", "AMD64"):
        pytest.skip(f"the packed forms named here are x86-64's, this is "
                    f"{platform.machine()}")
    if os.environ.get("CXXFLAGS"):
        pytest.skip("CXXFLAGS is overridden in the environment: the "
                    "binary is not the Makefile's")
    try:
        build_native(variant=variant)
    except RuntimeError as e:
        if not variant:
            raise
        pytest.skip(f"no {variant} build here: "
                    f"{str(e).strip().splitlines()[-1]}")
    out = subprocess.run(
        ["objdump", "-d", "-C", "--no-show-raw-insn", server_binary(variant)],
        capture_output=True, text=True, check=True).stdout
    funcs: dict[str, list[str]] = {}
    name = None
    for line in out.splitlines():
        head = re.match(r"^[0-9a-f]+ <(.*)>:$", line)
        if head:
            m = re.match(r"distlr::loops::(\w+)\(", head.group(1))
            name = m.group(1) if m else "<other>"
            funcs.setdefault(name, [])
        elif name and "\t" in line:
            funcs[name].append(line.split("\t", 1)[1].strip())
    return funcs


@pytest.fixture(scope="module")
def release():
    return _disassembly("")


@pytest.fixture(scope="module")
def ubsan():
    return _disassembly("ubsan")


def _mnemonics(body: list[str]) -> set[str]:
    # a VEX build (an override, a later clone) spells them v<name>
    return {ins.split()[0].removeprefix("v") for ins in body if ins}


@pytest.mark.parametrize("loop", LOOPS)
def test_release_build_packs_the_loop(release, loop):
    assert loop in release, (
        f"no distlr::loops::{loop} in the binary: inlined away, so the "
        f"disassembly read here is not the code that runs")
    missing = [p for p in LOOPS[loop] if p not in _mnemonics(release[loop])]
    assert not missing, (
        f"{loop} holds no {missing}: the loop is scalar again (see the "
        f"comment over CXXFLAGS in ps/native/Makefile)")


@pytest.mark.parametrize("loop", LOOPS)
def test_release_build_fuses_nothing_in_the_loop(release, loop):
    fused = [ins for ins in release[loop] if FUSED.search(ins)]
    assert not fused, (
        f"{loop} holds a fused multiply-add ({fused[0]}): one rounding "
        f"where the reference has two; -ffp-contract=off is gone or an "
        f"FMA target came without it")


def test_release_build_fuses_nothing_anywhere(release):
    fused = [(name, ins) for name, body in release.items() for ins in body
             if FUSED.search(ins)]
    assert not fused, f"fused multiply-adds in the server: {fused[:3]}"


@pytest.mark.parametrize("loop", LOOPS)
def test_sanitizer_build_keeps_the_loop_scalar(ubsan, loop):
    """The variants are the scalar twin the bits are compared with
    (tests/test_ps_apply_bits.py): if they packed too, that comparison
    would hold a build to itself."""
    assert loop in ubsan
    packed = PACKED & _mnemonics(ubsan[loop])
    assert not packed, f"{loop} is packed in the ubsan build: {packed}"
    assert not [ins for ins in ubsan[loop] if FUSED.search(ins)]


def test_the_sanitizer_builds_ftrl_step_is_the_scalar_one(ubsan):
    """What stands in the packed step's place there is ``FtrlStepOne``
    four times over: scalar square roots and divides."""
    assert {"sqrtss", "divss"} <= _mnemonics(ubsan["FtrlStepPacked"])


def test_sanflags_define_what_keeps_the_twins_scalar():
    assert "-DDISTLR_SCALAR_LOOPS" in _flags("SANFLAGS")
    assert "-DDISTLR_SCALAR_LOOPS" not in _flags("CXXFLAGS")
    with open(os.path.join(native_dir(), "kv_loops.h")) as f:
        header = f.read()
    assert "!defined(DISTLR_SCALAR_LOOPS)" in header
    assert "<emmintrin.h>" in header and "immintrin" not in header


@pytest.mark.parametrize("var", ["CXXFLAGS", "SANFLAGS"])
def test_makefile_forbids_contraction_and_host_tuning(var):
    flags = _flags(var)
    assert "-ffp-contract=off" in flags
    banned = [f for f in flags
              if f in ("-ffast-math", "-Ofast", "-funsafe-math-optimizations",
                       "-fassociative-math", "-mfma")
              or f.startswith(("-march=", "-mtune=native", "-mcpu="))]
    assert not banned, f"{var} carries {banned}"


def test_makefile_says_why_contraction_is_off():
    lines = _makefile_lines()
    at = next(i for i, ln in enumerate(lines) if ln.startswith("CXXFLAGS"))
    comment = []
    for ln in reversed(lines[:at]):
        if not ln.startswith("#"):
            break
        comment.append(ln)
    text = " ".join(reversed(comment))
    assert "-ffp-contract=off" in text and "rounding" in text, (
        "the comment over CXXFLAGS must say what -ffp-contract=off is "
        "there for")


def test_release_flags_vectorise_and_sanitizer_flags_do_not():
    assert {"-ftree-vectorize", "-fvect-cost-model=dynamic"} <= set(
        _flags("CXXFLAGS"))
    assert "-O1" in _flags("SANFLAGS")
    assert not [f for f in _flags("SANFLAGS") if "vect" in f]
