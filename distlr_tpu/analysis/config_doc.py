"""Config <-> CLI <-> docs parity lint + ``docs/CONFIG.md`` generator.

The same bidirectional style as the metrics-doc lint (ISSUE 8), applied
to the configuration surface: every :class:`distlr_tpu.config.Config`
field must be reachable from the ``launch`` CLI (an ``add_argument``
whose dest is the field, or an audited NO_FLAG entry saying WHY not)
and documented in the generated ``docs/CONFIG.md``; and every doc row /
audit entry must still correspond to a live field.  A daemon's own
options are not fields: they are its subcommand's flags, which the
document names in a line each under the table.
Everything is read statically (``ast`` — no jax, no argparse import).

Regenerate the doc after changing Config or the CLI::

    python -m distlr_tpu.analysis --write-docs
"""

from __future__ import annotations

import ast
import os
import re

from distlr_tpu.analysis.report import Finding, repo_root

#: Config fields with deliberately NO CLI flag, each with the audit
#: reason (an entry for a field that gained a flag, or stopped
#: existing, is a finding).
NO_FLAG = {
    "sync_mode": "selected by the subcommand, not a flag: `launch sync` "
                 "is sync, `launch ps` is BSP, `launch ps --async` is "
                 "Hogwild",
    "l2_scale_by_batch": "per-quirk gate set via --compat-mode "
                         "(reference parity, SURVEY.md Q4); individual "
                         "flags would invite mixed quirk states the "
                         "parity suite never pins",
    "sync_last_gradient": "per-quirk gate set via --compat-mode (Q1)",
    "reference_rng_init": "per-quirk gate set via --compat-mode (Q2)",
    "wrap_final_batch": "per-quirk gate set via --compat-mode (Q5)",
    "dtype": "accumulation dtype is model-internal tuning pinned by the "
             "bench harness programmatically; the operational knob the "
             "CLI exposes is --feature-dtype",
    "compute_dtype": "matmul dtype, same class as dtype: bench-harness "
                     "tuning, not an operator knob",
    "mesh_shape": "derived from --num-workers x --feature-shards "
                  "(_config_from_args), never set directly",
}


def config_path() -> str:
    return os.path.join(repo_root(), "distlr_tpu", "config.py")


def launch_path() -> str:
    return os.path.join(repo_root(), "distlr_tpu", "launch.py")


def doc_path() -> str:
    return os.path.join(repo_root(), "docs", "CONFIG.md")


# ---------------------------------------------------------------------------
# static extraction
# ---------------------------------------------------------------------------


def config_fields(path: str | None = None) -> dict[str, dict]:
    """Config dataclass fields -> {line, default, help} — the help text
    harvested from the comment block above (or inline with) the field,
    the way the dataclass is actually documented."""
    path = path or config_path()
    with open(path) as f:
        src = f.read()
    lines = src.splitlines()
    tree = ast.parse(src, filename=path)
    cls = next(n for n in tree.body
               if isinstance(n, ast.ClassDef) and n.name == "Config")
    out: dict[str, dict] = {}
    for node in cls.body:
        if not isinstance(node, ast.AnnAssign) or not isinstance(
                node.target, ast.Name):
            continue
        name = node.target.id
        default = ast.unparse(node.value) if node.value is not None else ""
        # inline comment, else the contiguous # block immediately above
        text = lines[node.lineno - 1]
        m = re.search(r"#\s?(.*)$", text)
        help_parts: list[str] = []
        if m and not text.lstrip().startswith("#"):
            help_parts.append(m.group(1).strip())
        i = node.lineno - 2
        block: list[str] = []
        while i >= 0:
            stripped = lines[i].strip()
            if stripped.startswith("#") and not stripped.startswith("# --"):
                block.append(stripped.lstrip("#").strip())
                i -= 1
            else:
                break
        help_parts = list(reversed(block)) + help_parts
        out[name] = {
            "line": node.lineno,
            "default": default,
            "help": " ".join(p for p in help_parts if p),
        }
    return out


def launch_dests(path: str | None = None) -> dict[str, dict]:
    """Every ``add_argument`` in launch.py -> dest: {flag, line}.  When
    several subcommands reuse one dest, the first flag wins (they are
    the same knob by construction)."""
    path = path or launch_path()
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    out: dict[str, dict] = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"):
            continue
        flags = [a.value for a in node.args
                 if isinstance(a, ast.Constant) and isinstance(a.value, str)
                 and a.value.startswith("--")]
        if not flags:
            continue
        dest = None
        for kw in node.keywords:
            if kw.arg == "dest" and isinstance(kw.value, ast.Constant):
                dest = kw.value.value
        if dest is None:
            dest = flags[0].lstrip("-").replace("-", "_")
        out.setdefault(dest, {"flag": flags[0], "line": node.lineno})
    return out


def documented_fields(text: str | None = None) -> dict[str, str]:
    """docs/CONFIG.md rows -> {field: flag-column-text}."""
    if text is None:
        try:
            with open(doc_path()) as f:
                text = f.read()
        except OSError:
            return {}
    rows = re.findall(r"^\| `([a-z0-9_]+)` \| ([^|]*) \|", text,
                      flags=re.MULTILINE)
    return {name: flag.strip() for name, flag in rows}


# ---------------------------------------------------------------------------
# doc generation
# ---------------------------------------------------------------------------


def _flag_for(field: str, dests: dict[str, dict]) -> str | None:
    return dests[field]["flag"] if field in dests else None


def generate() -> str:
    fields = config_fields()
    dests = launch_dests()
    lines = [
        "# Config reference",
        "",
        "Every `distlr_tpu.config.Config` field, its `launch` CLI flag,",
        "default, and meaning.  GENERATED — do not edit by hand:",
        "",
        "    python -m distlr_tpu.analysis --write-docs",
        "",
        "regenerates this file from the dataclass + the launch parser;",
        "the config-parity lint (`python -m distlr_tpu.analysis`, tier-1",
        "via tests/test_analysis.py) fails the build when field, flag,",
        "and doc drift in any direction.  Fields marked *(no flag)* are",
        "audited as CLI-less in `distlr_tpu/analysis/config_doc.py`",
        "(NO_FLAG), each with its reason.",
        "",
        "| field | flag | default | meaning |",
        "|---|---|---|---|",
    ]
    for name, meta in fields.items():
        flag = _flag_for(name, dests)
        if flag is None:
            flag_txt = "*(no flag)*"
        else:
            flag_txt = f"`{flag}`"
        help_txt = meta["help"].replace("|", "\\|")
        if name in NO_FLAG:
            help_txt = (help_txt + " — *no flag:* "
                        + NO_FLAG[name].replace("|", "\\|")).strip(" —")
        default = meta["default"].replace("|", "\\|")
        lines.append(
            f"| `{name}` | {flag_txt} | `{default}` | {help_txt} |")
    lines += [
        "",
        "What only one daemon reads is not a `Config` field but a flag of its",
        "subcommand, whose default is the receiving constructor's:",
        "",
        "- `launch serve --help`: the scoring server, its hot reload and "
        "the feedback loop.",
        "- `launch route --help`: the serving router.",
        "- `launch autopilot --help`: the scaling daemon's tick, bands and "
        "bounds.",
        "- `launch obs-agg --help`: the aggregator's alert thresholds, SLO "
        "file and embedded tsdb.",
        "",
    ]
    return "\n".join(lines)


def write_doc() -> str:
    path = doc_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    text = generate()
    with open(path, "w") as f:
        f.write(text)
    return path


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------


def check() -> list[Finding]:
    fields = config_fields()
    dests = launch_dests()
    crel = os.path.relpath(config_path(), repo_root())
    lrel = os.path.relpath(launch_path(), repo_root())
    drel = os.path.relpath(doc_path(), repo_root())
    findings: list[Finding] = []

    # every field reaches the CLI, or carries an audited reason not to
    for name, meta in fields.items():
        if _flag_for(name, dests) is None and name not in NO_FLAG:
            findings.append(Finding(
                "config", f"config-no-flag:{name}",
                f"Config.{name} has no launch flag (no dest matches, no "
                "audited NO_FLAG reason)",
                ((crel, meta["line"]),)))

    # audit hygiene: NO_FLAG entries must stay live
    for field in NO_FLAG:
        if field not in fields:
            findings.append(Finding(
                "config", f"noflag-stale:{field}",
                f"NO_FLAG audits dead Config field {field!r}",
                ((crel, 1),)))
        elif field in dests:
            findings.append(Finding(
                "config", f"noflag-has-flag:{field}",
                f"NO_FLAG audits {field!r} as CLI-less but launch now "
                f"defines {dests[field]['flag']} — delete the entry",
                ((lrel, dests[field]["line"]),)))

    # doc sync, both directions (regenerate to fix)
    doc = documented_fields()
    if not doc:
        findings.append(Finding(
            "config", "config-doc-missing",
            "docs/CONFIG.md missing — run "
            "`python -m distlr_tpu.analysis --write-docs`",
            ((drel, 1),)))
        return findings
    for name, meta in fields.items():
        if name not in doc:
            findings.append(Finding(
                "config", f"undocumented-field:{name}",
                f"Config.{name} is missing from docs/CONFIG.md — "
                "regenerate it", ((crel, meta["line"]), (drel, 1))))
            continue
        flag = _flag_for(name, dests)
        want = f"`{flag}`" if flag else "*(no flag)*"
        if doc[name] != want:
            findings.append(Finding(
                "config", f"doc-flag-drift:{name}",
                f"docs/CONFIG.md lists {name} under {doc[name]!r} but "
                f"the CLI says {want!r} — regenerate",
                ((drel, 1),)))
    for name in doc:
        if name not in fields:
            findings.append(Finding(
                "config", f"stale-doc-row:{name}",
                f"docs/CONFIG.md documents {name} but Config has no such "
                "field — regenerate", ((drel, 1),)))
    return findings
