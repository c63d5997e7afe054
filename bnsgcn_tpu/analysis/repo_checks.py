"""Repo-level contract checks: non-Python surfaces the AST rules can't see.

Two checks ride every full-surface graftlint run (core.lint_paths):

* **tune-schedule-invalid** — every ``--tune-schedule`` string literal in
  ``scripts/*.sh`` and ``bench.py`` is parsed with the REAL
  ``tune.parse_schedule`` grammar at lint time. A typo'd schedule otherwise
  survives until the run it belongs to dies at startup.

* **config-doc-drift** — the README "Config knobs" table (between the
  ``knob-table:begin/end`` markers) must be byte-identical to what
  ``render_knob_table()`` generates from the live ``config.create_parser()``.
  Undocumented flags, stale flags, stale choices and stale defaults all
  fail the same way: the table is generated contract, not prose.
  Regenerate with::

      python -c "from bnsgcn_tpu.analysis.repo_checks import \\
                 write_knob_table; write_knob_table()"
"""

from __future__ import annotations

import ast
import glob
import os
import re

from bnsgcn_tpu.analysis.core import Finding

KNOB_BEGIN = "<!-- knob-table:begin (generated; see analysis/repo_checks.py) -->"
KNOB_END = "<!-- knob-table:end -->"

# --tune-schedule <spec> / --tune-schedule=<spec> in shell-ish text
_SH_SCHED_RE = re.compile(
    r"--tune[-_]schedule(?:=|\s+)(?:\"([^\"]*)\"|'([^']*)'|([^\s\"']+))")


def check_repo(root: str) -> list:
    return check_tune_schedules(root) + check_config_docs(root)


# ----------------------------------------------------------------------------
# satellite: --tune-schedule literals parse under the real grammar
# ----------------------------------------------------------------------------

def _schedule_literals_sh(path: str) -> list:
    """(line, spec) pairs for shell scripts / the watch queue."""
    out = []
    with open(path, errors="replace") as f:
        for ln, line in enumerate(f, 1):
            for m in _SH_SCHED_RE.finditer(line):
                spec = next(g for g in m.groups() if g is not None)
                out.append((ln, spec))
    return out


def _schedule_literals_py(path: str) -> list:
    """(line, spec) pairs for Python: `tune_schedule="..."` keywords /
    assignments, and string constants following a "--tune-schedule" (or
    embedded "--tune-schedule=...") element in argv-style lists."""
    with open(path, errors="replace") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError:
        return []
    out = []

    def lit(node):
        return (node.value if isinstance(node, ast.Constant)
                and isinstance(node.value, str) else None)

    for node in ast.walk(tree):
        if isinstance(node, ast.keyword) and node.arg == "tune_schedule":
            v = lit(node.value)
            if v is not None:
                out.append((node.value.lineno, v))
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "tune_schedule":
                    v = lit(node.value)
                    if v is not None:
                        out.append((node.value.lineno, v))
        elif isinstance(node, (ast.List, ast.Tuple)):
            elts = node.elts
            for i, el in enumerate(elts):
                v = lit(el)
                if v is None:
                    continue
                if v in ("--tune-schedule", "--tune_schedule"):
                    if i + 1 < len(elts):
                        nxt = lit(elts[i + 1])
                        if nxt is not None:
                            out.append((elts[i + 1].lineno, nxt))
                else:
                    m = _SH_SCHED_RE.search(v)
                    if m:
                        spec = next(g for g in m.groups() if g is not None)
                        out.append((el.lineno, spec))
    return out


def check_tune_schedules(root: str) -> list:
    from bnsgcn_tpu.config import ConfigError
    from bnsgcn_tpu.tune import parse_schedule
    targets = sorted(glob.glob(os.path.join(root, "scripts", "*.sh")))
    if os.path.exists(os.path.join(root, "bench.py")):
        targets.append(os.path.join(root, "bench.py"))
    out = []
    for path in targets:
        rel = os.path.relpath(path, root)
        lits = (_schedule_literals_py(path) if path.endswith(".py")
                else _schedule_literals_sh(path))
        for ln, spec in lits:
            if not spec:
                continue            # empty string is the documented default
            try:
                parse_schedule(spec)
            except ConfigError as ex:
                out.append(Finding(
                    rel, ln, 0, "tune-schedule-invalid",
                    f"--tune-schedule literal {spec!r} rejected by "
                    f"tune.parse_schedule: {ex}"))
    return out


# ----------------------------------------------------------------------------
# satellite: README knob table == config.create_parser()
# ----------------------------------------------------------------------------

def _parser_rows() -> list:
    """One (flag, default, choices) row per CLI knob, kebab spelling (the
    snake alias documents itself), --help excluded, argparse insertion
    order preserved. The prose explanations live in the quick-start knob
    walkthrough and the Config dataclass comments; THIS table is the
    machine-checked flag/choices contract."""
    from bnsgcn_tpu.config import create_parser
    rows = []
    for action in create_parser()._actions:
        opts = [o for o in action.option_strings if o.startswith("--")]
        if not opts or opts[0] == "--help":
            continue
        flag = opts[0]
        default = action.default
        if default is None or default == "":
            default = ""
        elif default is False:
            default = "off"
        elif default is True:
            default = "on"
        choices = " ".join(f"`{c}`" for c in action.choices) \
            if action.choices is not None else ""
        rows.append((flag, str(default), choices))
    return rows


def render_knob_table() -> str:
    lines = [KNOB_BEGIN,
             "| knob | default | choices |",
             "|---|---|---|"]
    for flag, default, choices in _parser_rows():
        d = f"`{default}`" if default != "" else ""
        lines.append(f"| `{flag}` | {d} | {choices} |")
    lines.append(KNOB_END)
    return "\n".join(lines) + "\n"


def _find_block(text: str):
    """(start_line, end_line, block_text) of the marked README region,
    1-indexed inclusive; None when the markers are absent."""
    lines = text.splitlines()
    try:
        b = next(i for i, l in enumerate(lines) if l.strip() == KNOB_BEGIN)
        e = next(i for i, l in enumerate(lines) if l.strip() == KNOB_END)
    except StopIteration:
        return None
    return b + 1, e + 1, "\n".join(lines[b:e + 1]) + "\n"


def check_config_docs(root: str, readme: str = "README.md") -> list:
    path = os.path.join(root, readme)
    if not os.path.exists(path):
        return []
    with open(path, errors="replace") as f:
        text = f.read()
    block = _find_block(text)
    if block is None:
        return [Finding(readme, 1, 0, "config-doc-drift",
                        f"README has no '{KNOB_BEGIN}' .. '{KNOB_END}' "
                        f"knob table — run write_knob_table() to add it")]
    start, _end, got = block
    want = render_knob_table()
    if got == want:
        return []
    got_l, want_l = got.splitlines(), want.splitlines()
    at = next((i for i in range(min(len(got_l), len(want_l)))
               if got_l[i] != want_l[i]), min(len(got_l), len(want_l)))
    detail = (f"first drift at table line {at + 1}: README has "
              f"{got_l[at] if at < len(got_l) else '<missing>'!r}, parser "
              f"says {want_l[at] if at < len(want_l) else '<removed>'!r}")
    return [Finding(readme, start + at, 0, "config-doc-drift",
                    f"README knob table drifted from config.create_parser() "
                    f"({len(got_l)} vs {len(want_l)} lines); {detail}")]


def write_knob_table(root: str | None = None, readme: str = "README.md"):
    """Regenerate the marked README block in place (or append a fresh one
    at the end when no markers exist yet)."""
    from bnsgcn_tpu.analysis.core import resolve_root
    path = os.path.join(resolve_root(root), readme)
    with open(path, errors="replace") as f:
        text = f.read()
    block = _find_block(text)
    want = render_knob_table()
    if block is None:
        text = text.rstrip("\n") + "\n\n" + want
    else:
        lines = text.splitlines(keepends=True)
        b, e = block[0] - 1, block[1]
        text = "".join(lines[:b]) + want + "".join(lines[e:])
    with open(path, "w") as f:
        f.write(text)
    print(f"knob table written to {path}")
