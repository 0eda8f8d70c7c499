"""The launcher's flags, their defaults and their checks (ISSUE 55).

A daemon's options are its subcommand's flags and go from ``args``
straight to the constructor that uses them; a flag reaches ``Config``
only when its ``dest`` is a field's name.  These tests hold the flags
themselves to a table taken from the commit before that change, each
moved option's effective default to the literal ``Config`` carried, and
each moved check to the CLI contract (``error: ...``, exit code 2).
"""

import argparse
import dataclasses
import importlib
import inspect
import json
import os
import signal

import pytest

from distlr_tpu import launch
from distlr_tpu.config import Config

_TABLE = os.path.join(os.path.dirname(__file__), "data", "launch_flags.json")


def _subparsers() -> dict[str, argparse.ArgumentParser]:
    parser = launch.build_parser()
    sub = next(a for a in parser._actions
               if isinstance(a, argparse._SubParsersAction))
    return dict(sub.choices)


def _describe(p: argparse.ArgumentParser) -> list[dict]:
    """One dict an action (``-h`` left out): what the table holds."""
    return [{
        "flags": list(a.option_strings),
        "dest": a.dest,
        "action": type(a).__name__,
        "type": getattr(a.type, "__name__", None) if a.type else None,
        "choices": list(a.choices) if a.choices is not None else None,
        "nargs": a.nargs,
        "const": a.const,
        "default": a.default,
        "required": a.required,
        "metavar": a.metavar,
        "help": a.help,
    } for a in p._actions if not isinstance(a, argparse._HelpAction)]


with open(_TABLE) as _f:
    _PARENT = json.load(_f)

#: the 50 fields that left ``Config``: the field, the literal default the
#: parent's ``config.py`` gave it, the subcommand and ``dest`` that carry
#: it now, and where the default lives when the parser leaves ``None``
#: (``module:Callable.parameter`` or ``module:CONSTANT``; ``None`` when
#: ``None`` itself is the default)
_ROUTER = "distlr_tpu.serve.router:ScoringRouter."
_SERVER = "distlr_tpu.serve.server:ScoringServer."
_ENGINE = "distlr_tpu.serve.engine:ScoringEngine."
_WATCHER = "distlr_tpu.serve.reload:LivePSWatcher."
_SINK = "distlr_tpu.feedback.sink:FeedbackSink."
_POLICY = "distlr_tpu.autopilot.policy:PolicyConfig."
_DAEMON = "distlr_tpu.autopilot.daemon:AutopilotDaemon."
_SCRAPER = "distlr_tpu.obs.federate:FleetScraper."
MOVED = [
    ("serve_port", 0, "serve", "port", _SERVER + "port"),
    ("serve_host", "127.0.0.1", "serve", "bind", _SERVER + "host"),
    ("serve_max_batch_size", 1024, "serve", "serve_max_batch_size",
     _ENGINE + "max_batch_size"),
    ("serve_max_wait_ms", 2.0, "serve", "max_wait_ms",
     _SERVER + "max_wait_ms"),
    ("serve_reload_interval_s", 1.0, "serve", "reload_interval",
     "distlr_tpu.serve.reload:HotReloader.interval_s"),
    ("serve_hot_rows", 0, "serve", "hot_rows", None),
    ("serve_hot_min_coverage", 0.95, "serve", "hot_min_coverage",
     _WATCHER + "min_coverage"),
    ("serve_hot_full_every", 10, "serve", "hot_full_every",
     _WATCHER + "full_refresh_every"),
    ("serve_engine_idle_evict_s", 0.0, "serve", "engine_idle_evict",
     _ENGINE + "idle_evict_s"),
    ("serve_model_id", "default", "serve", "model_id",
     "distlr_tpu.serve.tenant:DEFAULT_MODEL"),
    ("feedback_spool_dir", None, "serve", "feedback_spool", None),
    ("feedback_shard_dir", None, "serve", "feedback_shards", None),
    ("feedback_window_s", 60.0, "serve", "feedback_window",
     _SINK + "window_s"),
    ("feedback_negative_rate", 0.1, "serve", "feedback_negative_rate", None),
    ("feedback_shard_records", 1024, "serve", "feedback_shard_records",
     _SINK + "shard_records"),
    ("feedback_capacity", 100_000, "serve", "feedback_capacity",
     _SINK + "capacity"),
    ("feedback_drift_block", 512, "serve", "drift_block",
     _SINK + "drift_block"),
    ("feedback_drift_threshold", 0.25, "serve", "drift_threshold",
     _SINK + "drift_threshold"),
    ("route_quota", None, "route", "quota", _ROUTER + "quotas"),
    ("route_port", 0, "route", "port", _ROUTER + "port"),
    ("route_host", "127.0.0.1", "route", "bind", _ROUTER + "host"),
    ("route_max_inflight", 64, "route", "max_inflight",
     _ROUTER + "max_inflight"),
    ("route_eject_after", 3, "route", "eject_after", _ROUTER + "eject_after"),
    ("route_health_interval_s", 1.0, "route", "health_interval",
     _ROUTER + "health_interval_s"),
    ("route_probe_backoff_s", 0.5, "route", "probe_backoff",
     _ROUTER + "probe_backoff_s"),
    ("route_probe_backoff_max_s", 30.0, "route", "probe_backoff_max",
     _ROUTER + "probe_backoff_max_s"),
    ("route_backend_timeout_s", 30.0, "route", "backend_timeout",
     _ROUTER + "backend_timeout_s"),
    ("autopilot_interval_s", 2.0, "autopilot", "autopilot_interval_s",
     _DAEMON + "interval_s"),
    ("autopilot_rate_window_s", 10.0, "autopilot", "autopilot_rate_window_s",
     _DAEMON + "rate_window_s"),
    *((f"autopilot_{name}", default, "autopilot", f"autopilot_{name}",
       _POLICY + name) for name, default in (
        ("hysteresis_ticks", 2), ("cooldown_s", 10.0),
        ("rollback_window_s", 60.0), ("ps_min", 1), ("ps_max", 8),
        ("engine_min", 1), ("engine_max", 8), ("worker_min", 1),
        ("worker_max", 8), ("staleness_high", 64.0),
        ("push_rate_high", 200.0), ("push_rate_low", 20.0),
        ("shed_rate_high", 0.5), ("route_p99_high_ms", 250.0),
        ("req_rate_low", 5.0), ("lag_high", 4.0), ("lag_low", 1.0))),
    ("slo_file", None, "obs-agg", "slo_file", None),
    ("obs_tsdb_raw_points", 512, "obs-agg", "obs_tsdb_raw_points",
     _SCRAPER + "tsdb_raw_points"),
    ("obs_tsdb_rollup_retention_s", 3600.0, "obs-agg",
     "obs_tsdb_rollup_retention_s", _SCRAPER + "tsdb_rollup_retention_s"),
    ("obs_tsdb_history_lines", 2000, "obs-agg", "obs_tsdb_history_lines",
     "distlr_tpu.obs.federate:HISTORY_MAX_LINES"),
]
_MOVED_DESTS = {(cmd, dest) for _f, _d, cmd, dest, _w in MOVED}

#: what a subcommand's parser needs before it parses at all
_REQUIRED = {"route": ["--replicas", "127.0.0.1:1"]}


@pytest.mark.parametrize("cmd", sorted(_PARENT["subcommands"]))
def test_a_subcommands_flags_are_the_parents(cmd):
    """Option strings, dest, type, choices, nargs, help and the rest of
    each action equal the table; so does a default, but for the moved
    options, whose default the next test holds where it lives now."""
    want = [dict(_PARENT["rows"][i]) for i in _PARENT["subcommands"][cmd]]
    got = _describe(_subparsers()[cmd])
    assert [g["dest"] for g in got] == [w["dest"] for w in want]
    for g, w in zip(got, want, strict=True):
        if (cmd, g["dest"]) in _MOVED_DESTS:
            g, w = dict(g, default=None), dict(w, default=None)
        assert g == w


def test_the_table_names_every_subcommand():
    assert sorted(_subparsers()) == sorted(_PARENT["subcommands"])
    assert len(_PARENT["subcommands"]) == 22


def _default_at(where: str):
    module, _, path = where.partition(":")
    obj = importlib.import_module(module)
    name, _, param = path.partition(".")
    obj = getattr(obj, name)
    if not param:
        return obj
    return inspect.signature(obj).parameters[param].default


@pytest.mark.parametrize("field,literal,cmd,dest,where", MOVED,
                         ids=[m[0] for m in MOVED])
def test_a_moved_options_effective_default_is_configs(
        field, literal, cmd, dest, where):
    """What the owning constructor receives when the flag is not given:
    the parser's default, or where that is ``None`` and the call leaves
    the argument out, the constructor's own."""
    assert field not in {f.name for f in dataclasses.fields(Config)}
    args = launch.build_parser().parse_args([cmd, *_REQUIRED.get(cmd, [])])
    value = getattr(args, dest)
    if value is None and where is not None:
        value = _default_at(where)
    assert value == literal and type(value) is type(literal)


@pytest.fixture
def cli(tmp_path, monkeypatch, capsys):
    """Run ``launch.main`` in this process: its exit code and stderr.  A
    ``cmd_*`` installs a SIGTERM handler for its daemon; a test's
    process keeps its own."""
    monkeypatch.setattr(signal, "signal", lambda *a: None)
    model = tmp_path / "model.txt"
    model.write_text("4\n0.1 0.2 0.3 0.4\n")
    words = {"MODEL": str(model), "DIR": str(tmp_path)}

    def run(argv):
        rc = launch.main([words.get(a, a) for a in argv])
        return rc, capsys.readouterr().err
    return run


_SERVE = ["serve", "--num-feature-dim", "4", "--model-file", "MODEL"]
_LIVE = ["serve", "--num-feature-dim", "4", "--ps-hosts", "127.0.0.1:1"]
_ROUTE = ["route", "--replicas", "127.0.0.1:1"]
_PILOT = ["autopilot", "--fleet", "http://127.0.0.1:1", "--unwatched",
          "--worker-cmd", "true {worker_id}"]
_AGG = ["obs-agg", "--obs-run-dir", "DIR"]
#: a subcommand that would start, a bad value for each check that left
#: ``Config.__post_init__``, and the name the message carries (the flag's,
#: or the receiving parameter's)
REFUSED = [
    (_SERVE, ["--port", "70000"], "port"),
    (_SERVE, ["--max-wait-ms", "-1"], "max_wait_ms"),
    (_SERVE, ["--serve-max-batch-size", "0"], "max_batch_size"),
    (_SERVE, ["--engine-idle-evict", "-1"], "idle_evict"),
    (_SERVE, ["--hot-rows", "-1"], "--hot-rows"),
    (_SERVE, ["--model-id", "a b"], "model_id"),
    (_SERVE, ["--checkpoint-dir", "DIR", "--reload-interval", "0"],
     "interval_s"),
    (_LIVE, ["--hot-rows", "4", "--hot-min-coverage", "0"], "min_coverage"),
    (_LIVE, ["--hot-full-every", "-1"], "full_refresh_every"),
    (_SERVE, ["--feedback-spool", "DIR", "--feedback-window", "0"],
     "window_s"),
    (_SERVE, ["--feedback-spool", "DIR", "--feedback-negative-rate", "2"],
     "negative_rate"),
    (_SERVE, ["--feedback-spool", "DIR", "--feedback-capacity", "0"],
     "capacity"),
    (_SERVE, ["--feedback-spool", "DIR", "--drift-block", "0"], "block"),
    (_ROUTE, ["--port", "-1"], "port"),
    (_ROUTE, ["--max-inflight", "0"], "max_inflight"),
    (_ROUTE, ["--eject-after", "0"], "eject_after"),
    (_ROUTE, ["--health-interval", "0"], "health_interval"),
    (_ROUTE, ["--probe-backoff", "2", "--probe-backoff-max", "1"],
     "probe_backoff"),
    (_ROUTE, ["--backend-timeout", "0"], "backend_timeout"),
    (_PILOT, ["--interval", "0"], "interval"),
    (_PILOT, ["--rate-window", "0"], "rate_window"),
    (_PILOT, ["--hysteresis-ticks", "0"], "hysteresis_ticks"),
    (_PILOT, ["--cooldown", "-1"], "cooldown"),
    (_PILOT, ["--rollback-window", "-1"], "rollback_window"),
    (_PILOT, ["--ps-min", "-1"], "ps_min"),
    (_PILOT, ["--engine-min", "3", "--engine-max", "2"], "engine_min"),
    (_PILOT, ["--worker-min", "9"], "worker_min"),
    (_PILOT, ["--push-rate-low", "5", "--push-rate-high", "5"],
     "push_rate_low"),
    (_PILOT, ["--lag-low", "5", "--lag-high", "4"], "lag_low"),
    (_PILOT, ["--staleness-high", "0"], "staleness_high"),
    (_PILOT, ["--shed-rate-high", "-1"], "shed_rate_high"),
    (_PILOT, ["--route-p99-high", "0"], "route_p99_high"),
    (_PILOT, ["--req-rate-low", "-1"], "req_rate_low"),
    (_AGG, ["--obs-tsdb-raw-points", "1"], "raw_points"),
    (_AGG, ["--obs-tsdb-rollup-retention-s", "0"], "rollup_retention_s"),
    (_AGG, ["--obs-tsdb-history-lines", "0"], "history_max_lines"),
]


@pytest.mark.parametrize(
    "base,bad,named", REFUSED,
    ids=[base[0] + "".join(w for w in bad if w.startswith("--"))
         for base, bad, _n in REFUSED])
def test_a_moved_check_still_refuses_its_bad_value(cli, base, bad, named):
    rc, err = cli(base + bad)
    assert rc == 2
    assert "error: " in err and named in err.split("error: ", 1)[1]


def test_a_flag_reaches_config_when_its_dest_is_a_field():
    parse = launch.build_parser().parse_args
    cfg = launch._config_from_args(parse(["ps", "--ps-timeout", "5"]))
    assert cfg.ps_timeout_ms == 5
    # a daemon's flag is parsed and stays its subcommand's own
    args = parse(["serve", "--port", "7001", "--feedback-window", "9"])
    assert (args.port, args.feedback_window) == (7001, 9.0)
    cfg = launch._config_from_args(args)
    assert not hasattr(cfg, "serve_port") and not hasattr(cfg, "port")
    assert cfg == launch._config_from_args(parse(["serve"]))
    assert len(dataclasses.fields(Config)) == 69


def test_the_autopilots_flags_become_its_policy(monkeypatch):
    """Flags to ``PolicyConfig`` by name, the rest its own defaults (the
    case ``PolicyConfig.from_config``'s test held through ``Config``)."""
    from distlr_tpu import autopilot
    from distlr_tpu.autopilot import PolicyConfig

    seen = {}

    class Stop(Exception):
        pass

    def daemon(policy, actuators, **kw):
        seen.update(kw, policy=policy.cfg)
        raise Stop

    monkeypatch.setattr(signal, "signal", lambda *a: None)
    monkeypatch.setattr(autopilot, "AutopilotDaemon", daemon)
    with pytest.raises(Stop):
        launch.main(_PILOT + ["--hysteresis-ticks", "5", "--engine-max", "3",
                              "--shed-rate-high", "0.125",
                              "--interval", "7"])
    assert seen["policy"] == PolicyConfig(
        hysteresis_ticks=5, engine_max=3, shed_rate_high=0.125)
    assert seen["policy"].bounds("engine") == (PolicyConfig.engine_min, 3)
    assert seen["interval_s"] == 7.0 and "rate_window_s" not in seen
