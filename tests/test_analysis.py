"""distlr-lint self-tests (ISSUE 13 tentpole).

Three kinds of coverage, per the acceptance criteria:

* the runner exits non-zero on a SEEDED wire-constant mismatch, a
  seeded unlocked-shared-write, and a seeded lock-order cycle (fixture
  trees built here — a lint that cannot fail is worse than no lint);
* the repo itself is CLEAN under every pass, with a baseline whose
  every entry carries a justification (hygiene is itself linted);
* regression tests for the two highest-severity concurrency fixes the
  first run of the pass produced (ChaosLink.stop's teardown race and
  MembershipCoordinator's unlocked epoch reads).
"""

from __future__ import annotations

import os
import shutil
import socket
import textwrap
import threading
import time

import pytest

from distlr_tpu.analysis import baseline, concurrency, config_doc, doc_paths, wire_parity
from distlr_tpu.analysis.__main__ import main as lint_main
from distlr_tpu.analysis.report import repo_root

REPO = repo_root()


# ---------------------------------------------------------------------------
# wire parity
# ---------------------------------------------------------------------------


def _wire_fixture(tmp_path, mutate_header=None, mutate_client=None,
                  mutate_spec=None, mutate_store=None):
    """A minimal tree the wire pass can run against: the real header +
    mirrors (the protocol model included — it is a framing site like
    any other), with optional seeded mutations."""
    for rel in ("distlr_tpu/ps/native", "distlr_tpu/compress"):
        os.makedirs(tmp_path / rel, exist_ok=True)
    for rel in ("distlr_tpu/ps/wire.py", "distlr_tpu/ps/client.py",
                "distlr_tpu/ps/membership.py", "distlr_tpu/ps/server.py",
                "distlr_tpu/ps/store.py",
                "distlr_tpu/compress/codecs.py",
                "distlr_tpu/chaos/proxy.py",
                "distlr_tpu/analysis/protocol/spec.py",
                "distlr_tpu/analysis/protocol/checker.py",
                "distlr_tpu/analysis/protocol/mutants.py",
                "distlr_tpu/analysis/protocol/conformance.py"):
        os.makedirs((tmp_path / rel).parent, exist_ok=True)
        shutil.copy(os.path.join(REPO, rel), tmp_path / rel)
    hdr = open(os.path.join(
        REPO, "distlr_tpu/ps/native/kv_protocol.h")).read()
    if mutate_header:
        hdr = mutate_header(hdr)
    (tmp_path / "distlr_tpu/ps/native/kv_protocol.h").write_text(hdr)
    if mutate_client:
        cpath = tmp_path / "distlr_tpu/ps/client.py"
        cpath.write_text(mutate_client(cpath.read_text()))
    if mutate_spec:
        spath = tmp_path / "distlr_tpu/analysis/protocol/spec.py"
        spath.write_text(mutate_spec(spath.read_text()))
    if mutate_store:
        spath = tmp_path / "distlr_tpu/ps/store.py"
        spath.write_text(mutate_store(spath.read_text()))
    return str(tmp_path)


class TestWireParity:
    def test_repo_is_clean(self):
        assert wire_parity.check() == []

    def test_header_parser_sees_the_protocol(self):
        hdr = wire_parity.parse_header()
        assert hdr["kMagic"][0] == 0xD157C0DE
        assert hdr["kEpoch"][0] == 8
        assert hdr["kStatsVals"][0] == 28
        assert hdr["kCapEpoch"][0] == 1 << 9       # 1ull << evaluation
        assert hdr["sizeof(MsgHeader)"][0] == 24   # static_assert twin

    def test_seeded_value_mismatch_fails(self, tmp_path):
        root = _wire_fixture(
            tmp_path,
            mutate_header=lambda h: h.replace(
                "kQuantBlock = 256", "kQuantBlock = 128"))
        keys = {f.key for f in wire_parity.check(root=root)}
        assert "value-mismatch:kQuantBlock" in keys

    def test_seeded_one_sided_constant_fails(self, tmp_path):
        root = _wire_fixture(
            tmp_path,
            mutate_header=lambda h: h.replace(
                "constexpr uint64_t kQuantBlock = 256;",
                "constexpr uint64_t kQuantBlock = 256;\n"
                "constexpr uint64_t kNewKnob = 7;"))
        keys = {f.key for f in wire_parity.check(root=root)}
        assert "header-only:kNewKnob" in keys

    def test_seeded_raw_literal_fails(self, tmp_path):
        root = _wire_fixture(
            tmp_path,
            mutate_client=lambda s: s.replace(
                "range(min(wire.MAX_VALS_PER_KEY, self.dim), 1, -1)",
                "range(min(4096, self.dim), 1, -1)"))
        keys = {f.key for f in wire_parity.check(root=root)}
        assert any(k.startswith("raw-literal:distlr_tpu/ps/client.py:"
                                "kMaxValsPerKey") for k in keys)

    def test_seeded_stats_fields_drift_fails(self, tmp_path):
        root = _wire_fixture(
            tmp_path,
            mutate_client=lambda s: s.replace('    "epoch",\n', ""))
        keys = {f.key for f in wire_parity.check(root=root)}
        assert "stats-fields-length" in keys

    def test_stats_fields_tail_out_of_the_headers_order_fails(self, tmp_path):
        """The additive tail's order is written down in the header's
        kStats comment alone: two tail names swapped in the client (the
        length still right) is the drift the length check cannot see."""
        def swap(s):
            a, b = '    "sync_hold_seconds",\n', '    "sync_spread_seconds",\n'
            assert a + b in s
            return s.replace(a + b, b + a)

        root = _wire_fixture(tmp_path, mutate_client=swap)
        keys = {f.key for f in wire_parity.check(root=root)}
        assert "stats-fields-tail-order" in keys
        assert "stats-fields-length" not in keys

    def test_protocol_model_is_a_framing_site(self, tmp_path):
        """ISSUE 14 satellite: a protocol literal re-inlined inside
        analysis/protocol/ fails the existing raw-literal lint like
        any other mirror module."""
        src = open(os.path.join(
            REPO, "distlr_tpu/analysis/protocol/spec.py")).read()
        assert "wire.MAGIC" in src  # the mutation below stays honest
        root = _wire_fixture(
            tmp_path,
            mutate_spec=lambda s: s.replace(
                "wire.HEADER_STRUCT.pack(wire.MAGIC,",
                "wire.HEADER_STRUCT.pack(0xD157C0DE,"))
        keys = {f.key for f in wire_parity.check(root=root)}
        assert any(
            k.startswith("raw-literal:distlr_tpu/analysis/protocol/"
                         "spec.py:kMagic") for k in keys), keys

    def test_seeded_store_constant_drift_fails(self, tmp_path):
        """ISSUE 20 satellite: the durable-store disk format is linted
        like the wire format — a ps/store.py constant that drifts from
        the native writer's header fails the parity pass."""
        root = _wire_fixture(
            tmp_path,
            mutate_store=lambda s: s.replace(
                "STORE_VERSION = 1", "STORE_VERSION = 2"))
        keys = {f.key for f in wire_parity.check(root=root)}
        assert "store-value-mismatch:kStoreVersion" in keys, keys

    def test_seeded_store_struct_size_drift_fails(self, tmp_path):
        """A struct format that no longer packs to the header's size
        constant (a field added on one side only) is caught too."""
        root = _wire_fixture(
            tmp_path,
            mutate_store=lambda s: s.replace(
                'WAL_RECORD_STRUCT = struct.Struct("<QIBBHI")',
                'WAL_RECORD_STRUCT = struct.Struct("<QIBBHII")'))
        keys = {f.key for f in wire_parity.check(root=root)}
        assert any(k.startswith("store-struct-size:WAL_RECORD_STRUCT")
                   for k in keys), keys

    def test_seeded_store_mirror_deletion_fails(self, tmp_path):
        """Deleting ps/store.py while the header still defines store
        constants is a loud finding, not a silently skipped pass."""
        root = _wire_fixture(tmp_path)
        os.remove(os.path.join(root, "distlr_tpu/ps/store.py"))
        keys = {f.key for f in wire_parity.check(root=root)}
        assert "store-mirror-missing" in keys, keys


# ---------------------------------------------------------------------------
# concurrency
# ---------------------------------------------------------------------------


def _pkg(tmp_path, source: str) -> str:
    pkg = tmp_path / "fixture_pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(textwrap.dedent(source))
    return str(pkg)


class TestConcurrencyLint:
    def test_repo_is_clean_under_baseline(self):
        assert concurrency.check() == []

    def test_every_baseline_entry_has_a_justification(self):
        entries, problems = baseline.load_baseline()
        assert problems == []
        assert entries, "baseline unexpectedly empty"
        assert all(e.justification.strip() for e in entries)

    def test_seeded_unlocked_write_fails(self, tmp_path):
        pkg = _pkg(tmp_path, """
            import threading

            class Counter:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def safe_bump(self):
                    with self._lock:
                        self.n += 1

                def racy_bump(self):
                    self.n += 1
        """)
        fs = concurrency.check(pkg_dir=pkg,
                               baseline_path=str(tmp_path / "none.toml"))
        keys = {f.key for f in fs}
        assert any(k.startswith("unlocked-write:fixture_pkg/mod.py:"
                                "Counter.n:racy_bump") for k in keys)

    def test_seeded_lock_cycle_fails(self, tmp_path):
        pkg = _pkg(tmp_path, """
            import threading

            class A:
                def __init__(self, b: "B"):
                    self._lock = threading.Lock()
                    self.b = b

                def outer(self):
                    with self._lock:
                        self.b.enter()

                def enter(self):
                    with self._lock:
                        pass

            class B:
                def __init__(self, a: A):
                    self._lock = threading.Lock()
                    self.a = a

                def outer(self):
                    with self._lock:
                        self.a.enter()

                def enter(self):
                    with self._lock:
                        pass
        """)
        fs = concurrency.check(pkg_dir=pkg,
                               baseline_path=str(tmp_path / "none.toml"))
        assert any(f.key.startswith("lock-cycle:") for f in fs), \
            [f.key for f in fs]

    def test_locked_suffix_convention_is_understood(self, tmp_path):
        pkg = _pkg(tmp_path, """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.x = 0

                def bump(self):
                    with self._lock:
                        self._bump_locked()

                def _bump_locked(self):
                    self.x += 1
        """)
        fs = concurrency.check(pkg_dir=pkg,
                               baseline_path=str(tmp_path / "none.toml"))
        assert fs == [], [f.key for f in fs]

    def test_baseline_requires_justification(self, tmp_path):
        p = tmp_path / "b.toml"
        p.write_text('[[suppress]]\nkey = "unlocked-write:x"\n')
        _entries, problems = baseline.load_baseline(str(p))
        assert any(f.key.startswith("baseline-no-justification")
                   for f in problems)

    def test_stale_baseline_entry_fails(self, tmp_path):
        pkg = _pkg(tmp_path, "class Empty:\n    pass\n")
        p = tmp_path / "b.toml"
        p.write_text('[[suppress]]\nkey = "unlocked-write:gone"\n'
                     'justification = "was real once"\n'
                     'schedcheck_scenario = "-"\n')
        fs = concurrency.check(pkg_dir=pkg, baseline_path=str(p))
        assert any(f.key.startswith("baseline-stale:") for f in fs)


# ---------------------------------------------------------------------------
# config / docs parity + the runner
# ---------------------------------------------------------------------------


class TestConfigDocLint:
    def test_repo_is_clean(self):
        assert config_doc.check() == []

    def test_doc_is_current(self):
        with open(config_doc.doc_path()) as f:
            assert f.read() == config_doc.generate(), \
                "docs/CONFIG.md stale — run " \
                "`python -m distlr_tpu.analysis --write-docs`"

    def test_cli_reaches_new_fields(self):
        """The drift this lint fixed on day one must stay fixed: the
        fields that had silently lost (or never had) flags."""
        dests = config_doc.launch_dests()
        for field in ("random_seed", "ps_timeout_ms", "prefetch"):
            assert field in dests, field


class TestDocPathsLint:
    @pytest.mark.parametrize("doc", doc_paths.DOCS)
    def test_document_names_what_exists(self, doc):
        """Every path under this repo's directories and every `make`
        target the document's code names is there."""
        assert [f.render() for f in doc_paths.check_doc(doc)] == []

    def test_a_mistyped_path_or_target_is_found(self, tmp_path):
        (tmp_path / "distlr_tpu").mkdir()
        (tmp_path / "distlr_tpu" / "sync.py").write_text("")
        (tmp_path / "Makefile").write_text("lint:\n\ttrue\n")
        (tmp_path / "DOC.md").write_text(
            "`distlr_tpu/sync` and `distlr_tpu/sync.py:12` and\n"
            "`distlr_tpu/*.py` exist; `src/lr.cc` is the reference's.\n"
            "`distlr_tpu/synk.py` does not, nor `make -C tests lint`.\n"
            "```bash\nmake lint\nmake lint-all  # no such target\n```\n")
        keys = [f.key for f in doc_paths.check_doc("DOC.md", str(tmp_path))]
        assert keys == ["missing-target:DOC.md:.:lint-all",
                        "missing-path:DOC.md:distlr_tpu/synk.py",
                        "missing-target:DOC.md:tests:lint"]


class TestRunner:
    def test_all_passes_clean_on_repo(self, capsys):
        assert lint_main([]) == 0
        assert "clean" in capsys.readouterr().out

    def test_single_pass_selection(self, capsys):
        assert lint_main(["--pass", "wire"]) == 0
        out = capsys.readouterr().out
        assert "wire" in out and "concurrency" not in out


# ---------------------------------------------------------------------------
# regression tests for the two fixed concurrency findings
# ---------------------------------------------------------------------------


class _ProbeLock:
    """Context-manager lock stand-in recording acquisitions."""

    def __init__(self):
        self.acquired = 0

    def __enter__(self):
        self.acquired += 1
        return self

    def __exit__(self, *exc):
        return False


class TestConcurrencyFixes:
    def test_membership_epoch_reads_under_lock(self):
        """`unlocked-read:...MembershipCoordinator._epoch:epoch` — the
        published epoch view must take the coordinator lock (resize
        commits it from another thread)."""
        from distlr_tpu.ps.membership import MembershipCoordinator

        coord = MembershipCoordinator.__new__(MembershipCoordinator)
        coord._lock = _ProbeLock()
        coord._epoch = 7
        assert coord.epoch == 7
        assert coord._lock.acquired == 1

    def test_chaos_stop_reaps_storming_connections(self):
        """`unlocked-read:...ChaosLink._threads:stop` — stop() used to
        snapshot conns/threads BEFORE joining the accept loop (and read
        _threads without the lock), so a connection accepted
        concurrently with stop() could leak pump threads and sockets
        past stop().  Post-fix invariant: after stop() returns under a
        connect storm, the accept thread and every pump thread are
        dead."""
        from distlr_tpu.chaos.plan import FaultPlan
        from distlr_tpu.chaos.proxy import ChaosFabric

        # upstream: accept-and-hold echo-nothing server
        upstream = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        upstream.bind(("127.0.0.1", 0))
        upstream.listen(64)
        upstream.settimeout(0.1)
        up_conns: list[socket.socket] = []
        up_stop = threading.Event()

        def up_loop():
            while not up_stop.is_set():
                try:
                    c, _ = upstream.accept()
                    up_conns.append(c)
                except socket.timeout:
                    continue
                except OSError:
                    return

        up_thread = threading.Thread(target=up_loop, daemon=True)
        up_thread.start()
        port = upstream.getsockname()[1]

        try:
            for _round in range(3):
                fabric = ChaosFabric([("127.0.0.1", port)],
                                     FaultPlan(faults=[]))
                link = fabric.links[0]
                storm_stop = threading.Event()

                def storm():
                    while not storm_stop.is_set():
                        try:
                            with socket.create_connection(
                                    ("127.0.0.1", link.port),
                                    timeout=0.5) as s:
                                s.sendall(b"x" * 8)
                        except OSError:
                            return

                stormers = [threading.Thread(target=storm, daemon=True)
                            for _ in range(4)]
                for t in stormers:
                    t.start()
                time.sleep(0.05)  # let connections churn
                fabric.stop()
                # the fixed invariant: nothing survives stop()
                assert not link._accept_thread.is_alive()
                assert not any(t.is_alive() for t in link._threads), \
                    "pump thread leaked past stop()"
                storm_stop.set()
                for t in stormers:
                    t.join(timeout=5)
        finally:
            up_stop.set()
            try:
                upstream.close()
            except OSError:
                pass
            up_thread.join(timeout=5)
            for c in up_conns:
                try:
                    c.close()
                except OSError:
                    pass


# ---------------------------------------------------------------------------
# the Makefile entry point
# ---------------------------------------------------------------------------


@pytest.mark.skipif(shutil.which("make") is None, reason="no make")
def test_make_lint_target_exists():
    with open(os.path.join(REPO, "Makefile")) as f:
        text = f.read()
    assert "lint:" in text and "distlr_tpu.analysis" in text
