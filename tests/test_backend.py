"""The two rules of ``distlr_tpu/utils``: where the program runs and
keeps its compiled programs (``backend``), and when a native artifact is
rebuilt (``native_build``)."""

import os
import shutil
import subprocess
import sys

import pytest

from distlr_tpu.utils import backend, native_build

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_RESOLVE = (
    "import jax\n"
    "from distlr_tpu.utils.backend import configure_compile_cache\n"
    "if {forbid_update}:\n"
    "    def _no(*a, **k): raise AssertionError('config.update called')\n"
    "    jax.config.update = _no\n"
    "print(configure_compile_cache())\n"
    "print(configure_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def _resolve(cwd, env_dir=None):
    env = {k: v for k, v in os.environ.items() if k != backend.CACHE_ENV}
    env["PYTHONPATH"] = REPO
    if env_dir is not None:
        env[backend.CACHE_ENV] = env_dir
    r = subprocess.run(
        [sys.executable, "-c", _RESOLVE.format(forbid_update=env_dir is not None)],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split()


class TestCompileCachePlacement:
    def test_placed_from_outside_nothing_is_set_in_code(self, tmp_path):
        placed = str(tmp_path / "placed")
        # jax.config.update is booby-trapped in the child: JAX reads the
        # variable itself
        assert _resolve(str(tmp_path), env_dir=placed) == [placed] * 3
        assert not os.path.exists(os.path.join(str(tmp_path), ".jax_cache"))

    def test_default_is_the_checkout_and_does_not_move(self, tmp_path):
        want = os.path.join(REPO, ".jax_cache")
        assert backend.DEFAULT_CACHE_DIR == want
        # two calls, two processes, two working directories: one path
        assert _resolve(str(tmp_path)) == [want] * 3
        assert _resolve(REPO) == [want] * 3


class TestDeviceRule:
    def test_full_size_modes_refuse_the_cpu(self):
        with pytest.raises(SystemExit) as e:
            backend.require_tpu("some_bench.py")
        assert "platform=cpu" in str(e.value) and e.value.code != 0

    def test_chip_smoke_fails_without_a_chip(self):
        """chip_smoke.py is the chip's check: on the CPU it exits non-zero
        before any leg and prints neither PASS nor a result line."""
        r = subprocess.run(
            [sys.executable, "chip_smoke.py"], cwd=REPO, capture_output=True,
            text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu", "DISTLR_CPU_DEVICES": "1"})
        assert r.returncode != 0
        assert "PASS" not in r.stdout and "PASS" not in r.stderr
        assert r.stdout.strip() == ""
        assert "no TPU" in r.stderr

    def test_summary_is_what_jax_reports(self):
        import jax

        d = backend.device_summary()
        assert d == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                     "count": len(jax.devices())}

    @pytest.mark.parametrize("n", [1, 2, 4, 8])
    def test_dryrun_multichip_on_virtual_devices(self, n, capsys):
        """The driver's entry point raises unless one sharded step on an
        n-device mesh equals the single-device step, in every family."""
        import __graft_entry__

        __graft_entry__.dryrun_multichip(n)
        mesh = ({"data": n // 2, "model": 2} if n > 1 and n % 2 == 0
                else {"data": n})
        assert f"dryrun_multichip({n}): mesh={mesh} " in capsys.readouterr().out

    def test_dryrun_does_not_invent_devices(self):
        import jax

        import __graft_entry__

        with pytest.raises(RuntimeError, match="need .* devices, have"):
            __graft_entry__._ensure_devices(len(jax.devices()) + 1)

    def test_imports_start_no_backend(self):
        """Importing the package, the launcher and the online trainer
        takes no chip: a backend starts at first use, not at import."""
        r = subprocess.run(
            [sys.executable, "-c",
             "import distlr_tpu, distlr_tpu.launch, distlr_tpu.train, "
             "distlr_tpu.serve, distlr_tpu.serve.engine, distlr_tpu.ops, "
             "distlr_tpu.feedback.online\n"
             "from jax._src import xla_bridge\n"
             "assert not xla_bridge.backends_are_initialized()\n"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]

    def test_load_generator_imports_without_jax(self):
        """A sender must never take a chip: its import pulls in no jax."""
        r = subprocess.run(
            [sys.executable, "-c",
             "import sys, distlr_tpu.serve.loadgen as lg\n"
             "assert callable(lg.run_load) and callable(lg.make_payloads)\n"
             "assert 'jax' not in sys.modules, 'jax imported'\n"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]


needs_toolchain = pytest.mark.skipif(
    shutil.which("make") is None or shutil.which("g++") is None,
    reason="no native toolchain")


@needs_toolchain
class TestNativeBuildStamp:
    @pytest.fixture
    def tree(self, tmp_path):
        src = os.path.join(REPO, "distlr_tpu", "data", "native")
        for name in ("Makefile", "libsvm_parser.cc"):
            shutil.copy(os.path.join(src, name), tmp_path / name)
        return str(tmp_path), str(tmp_path / "libdistlr_libsvm.so")

    def test_stamp_decides_not_file_times(self, tree):
        src_dir, so = tree
        native_build.ensure_built(src_dir, [so])
        digest = native_build.read_stamp(so)
        assert digest == native_build.source_digest(src_dir)
        built_at = os.stat(so).st_mtime_ns

        native_build.ensure_built(src_dir, [so])  # fresh: no rebuild
        assert os.stat(so).st_mtime_ns == built_at

        # an edit to the source rebuilds without `rm`, although the
        # artifact is made to look newer than the source (a copied tree)
        with open(os.path.join(src_dir, "libsvm_parser.cc"), "a") as f:
            f.write("// edited\n")
        os.utime(so, ns=(built_at + 10**12, built_at + 10**12))
        native_build.ensure_built(src_dir, [so])
        assert native_build.read_stamp(so) not in (None, digest)
        assert os.stat(so).st_mtime_ns != built_at + 10**12

        # an artifact no stamp describes is not trusted
        os.remove(so + ".stamp")
        rebuilt_at = os.stat(so).st_mtime_ns
        native_build.ensure_built(src_dir, [so])
        assert native_build.read_stamp(so) is not None
        assert os.stat(so).st_mtime_ns != rebuilt_at

    def test_failed_build_is_an_error(self, tree):
        src_dir, so = tree
        with open(os.path.join(src_dir, "libsvm_parser.cc"), "a") as f:
            f.write("this is not C++\n")
        with pytest.raises(RuntimeError, match="native build failed"):
            native_build.ensure_built(src_dir, [so])
        assert not os.path.exists(so + ".stamp")
