"""Repo-source static lint as a fast-lane test (ISSUE 14 satellite).

The authoritative linter is ruff, configured in pyproject.toml
([tool.ruff]) and run as its own tier1.yml step so lint failures never
mask test failures.  This test is the in-suite twin: when ruff is
installed it runs the real thing; otherwise it falls back to an
AST-based subset covering the same rule families (F401 unused imports,
F632 is-literal, E711/E712 None/bool comparisons, E713/E714 membership/
identity negation, E722 bare except, E741 ambiguous single-letter
names, F841 unused locals) so the fast lane still fails on a
regression instead of silently skipping — the container this repo
develops in does not ship ruff.
"""

import ast
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
LINT_PATHS = ("deepspeed_tpu", "tests", "chip_smoke.py")
# mirrors [tool.ruff.lint.per-file-ignores]: __init__ re-export surfaces
F401_EXEMPT = "__init__.py"


def _iter_sources():
    for root in LINT_PATHS:
        p = REPO / root
        files = [p] if p.is_file() else sorted(p.rglob("*.py"))
        for f in files:
            if "__pycache__" in f.parts or "build" in f.parts:
                continue
            yield f


def _unused_imports(tree):
    """F401 subset: module-wide unused import names.  Conservative on
    purpose — a name appearing in ANY Name node or string constant
    (string annotations, doctests, __all__) counts as used."""
    imported = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                name = (a.asname or a.name).split(".")[0]
                imported[name] = (node.lineno, a.name)
        elif isinstance(node, ast.ImportFrom):
            for a in node.names:
                if a.name != "*":
                    mod = f"{node.module}.{a.name}" if node.module else a.name
                    imported[a.asname or a.name] = (node.lineno, mod)
    if not imported:
        return []
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            for n in imported:
                if n in node.value:
                    used.add(n)
    return [(lineno, f"F401 `{mod}` imported as `{name}` but unused")
            for name, (lineno, mod) in sorted(imported.items(),
                                              key=lambda kv: kv[1][0])
            if name not in used]


def _comparison_findings(tree):
    """E711/E712 (== / != against None, True, False), E713/E714
    (`not x in y` / `not x is y`), F632 (`is` against a literal)."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Compare):
            for op, comp in zip(node.ops, node.comparators):
                if isinstance(op, (ast.Eq, ast.NotEq)) and isinstance(
                        comp, ast.Constant) and (
                        comp.value is None or comp.value is True
                        or comp.value is False):
                    code = "E711" if comp.value is None else "E712"
                    out.append((node.lineno,
                                f"{code} comparison to {comp.value!r} "
                                "with ==/!= (use `is`)"))
                if isinstance(op, (ast.Is, ast.IsNot)) and isinstance(
                        comp, (ast.Constant,)) and isinstance(
                        comp.value, (str, int, float, bytes, tuple)) \
                        and comp.value is not None \
                        and not isinstance(comp.value, bool):
                    out.append((node.lineno,
                                "F632 `is` comparison against a literal"))
        elif isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not) \
                and isinstance(node.operand, ast.Compare) \
                and len(node.operand.ops) == 1:
            inner = node.operand.ops[0]
            if isinstance(inner, ast.In):
                out.append((node.lineno,
                            "E713 `not x in y` (use `x not in y`)"))
            elif isinstance(inner, ast.Is):
                out.append((node.lineno,
                            "E714 `not x is y` (use `x is not y`)"))
    return out


def _bare_excepts(tree):
    return [(h.lineno, "E722 bare `except:`")
            for node in ast.walk(tree) if isinstance(node, ast.Try)
            for h in node.handlers if h.type is None]


_AMBIGUOUS = {"l", "O", "I"}


def _ambiguous_names(tree):
    """E741 subset: `l`/`O`/`I` bound as a variable, parameter, or
    exception name (including inside comprehensions and f-strings,
    which the ast sees even where tokenize does not)."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Name) and node.id in _AMBIGUOUS \
                and isinstance(node.ctx, ast.Store):
            out.append((node.lineno,
                        f"E741 ambiguous variable name `{node.id}`"))
        elif isinstance(node, ast.arg) and node.arg in _AMBIGUOUS:
            out.append((node.lineno,
                        f"E741 ambiguous parameter name `{node.arg}`"))
        elif isinstance(node, ast.ExceptHandler) \
                and node.name in _AMBIGUOUS:
            out.append((node.lineno,
                        f"E741 ambiguous exception name `{node.name}`"))
    return out


def _unused_locals(tree):
    """F841 subset: a simple `name = ...` statement inside a function
    whose name is never loaded anywhere in that function.  Conservative
    on purpose: skips underscore-prefixed names, tuple unpacking,
    augmented assigns, class bodies, and any function using
    locals()/exec/eval."""
    out = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        class_lines = set()
        escape_hatch = False
        for node in ast.walk(fn):
            if isinstance(node, ast.ClassDef):
                for inner in ast.walk(node):
                    if hasattr(inner, "lineno"):
                        class_lines.add(inner.lineno)
            elif isinstance(node, ast.Name) \
                    and node.id in ("locals", "vars", "exec", "eval"):
                escape_hatch = True
        if escape_hatch:
            continue
        assigned = {}
        loaded = set()
        strings = []
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.lineno not in class_lines:
                name = node.targets[0].id
                if not name.startswith("_"):
                    assigned.setdefault(name, node.lineno)
            elif isinstance(node, ast.Name) \
                    and isinstance(node.ctx, ast.Load):
                loaded.add(node.id)
            elif isinstance(node, (ast.Global, ast.Nonlocal)):
                loaded.update(node.names)
            elif isinstance(node, ast.Constant) \
                    and isinstance(node.value, str):
                strings.append(node.value)
        loaded.update(n for n in assigned
                      if any(n in s for s in strings))
        out.extend((lineno, f"F841 local `{name}` assigned but unused")
                   for name, lineno in sorted(assigned.items(),
                                              key=lambda kv: kv[1])
                   if name not in loaded)
    return out


_NOQA_RE = re.compile(r"#\s*noqa(?::\s*(?P<codes>[A-Z0-9, ]+))?",
                      re.IGNORECASE)


def _noqa_suppressed(line, code):
    """Mirror ruff's noqa semantics: bare `# noqa` kills every code on
    the line, `# noqa: F401,E402` only the listed ones."""
    m = _NOQA_RE.search(line)
    if not m:
        return False
    codes = m.group("codes")
    return codes is None or code in re.split(r"[,\s]+", codes.strip())


def _fallback_lint():
    findings = []
    for f in _iter_sources():
        text = f.read_text()
        tree = ast.parse(text, filename=str(f))
        src_lines = text.splitlines()
        rel = f.relative_to(REPO)
        hits = (_comparison_findings(tree) + _bare_excepts(tree)
                + _ambiguous_names(tree) + _unused_locals(tree))
        if f.name != F401_EXEMPT:
            hits += _unused_imports(tree)
        findings.extend(
            f"{rel}:{lineno}: {msg}" for lineno, msg in hits
            if not _noqa_suppressed(src_lines[lineno - 1],
                                    msg.split()[0]))
    return findings


def test_repo_sources_lint_clean():
    if shutil.which("ruff"):
        out = subprocess.run(
            ["ruff", "check", *LINT_PATHS], cwd=str(REPO),
            capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, (
            "ruff check failed:\n" + out.stdout + out.stderr)
        return
    findings = _fallback_lint()
    assert findings == [], (
        "repo-source lint (AST fallback for the pyproject [tool.ruff] "
        "set) found:\n  " + "\n  ".join(findings))


def test_fallback_linter_detects_each_rule(tmp_path):
    """The fallback must actually catch what it claims — one fixture
    per rule family, so a refactor cannot neuter the lint silently."""
    fixture = tmp_path / "bad.py"
    fixture.write_text(
        "import os\n"
        "x = 1\n"
        "if x == None:\n"
        "    pass\n"
        "if x == True:\n"
        "    pass\n"
        "if not x in (1, 2):\n"
        "    pass\n"
        "if not x is None:\n"
        "    pass\n"
        "if x is 'lit':\n"
        "    pass\n"
        "try:\n"
        "    pass\n"
        "except:\n"
        "    pass\n"
        "def f(l):\n"
        "    dead = l + 1\n"
        "    return l\n")
    tree = ast.parse(fixture.read_text())
    codes = {m.split()[0] for _ln, m in
             (_comparison_findings(tree) + _bare_excepts(tree)
              + _unused_imports(tree) + _ambiguous_names(tree)
              + _unused_locals(tree))}
    assert {"E711", "E712", "E713", "E714", "F632", "E722",
            "F401", "E741", "F841"} <= codes


def test_lint_scope_matches_pyproject():
    """The test and pyproject must lint the same surface."""
    try:
        import tomllib
    except ImportError:  # py310: tomllib is 3.11+
        import re
        text = (REPO / "pyproject.toml").read_text()
        m = re.search(r'^\s*select = \[(?P<body>[^\]]*)\]', text,
                      re.MULTILINE)
        assert m, "pyproject [tool.ruff.lint] select vanished"
        codes = set(re.findall(r'"([A-Z]\d+)"', m.group("body")))
    else:
        cfg = tomllib.loads((REPO / "pyproject.toml").read_text())
        codes = set(cfg["tool"]["ruff"]["lint"]["select"])
    assert {"F401", "F632", "E711", "E712", "E713", "E714",
            "E722", "E741", "F841"} == codes, (
        "pyproject ruff select drifted from the fallback's rule "
        "families — update tests/unit/test_repo_lint.py to match")
    assert sys.version_info >= (3, 10)


# Scripts PR 32 deleted because perf/ measures what they measured (the
# root benchmark script, the host-clock profilers and their harness).
_DELETED_SCRIPTS = re.compile(
    r"\bbench\.py\b|\bprofile_[\w*]+\.py\b|\b_harness\b")
# the records of what was, and this list
_MAY_NAME_THEM = {"CHANGES.md", "PERF.md", "ROADMAP.md", "ISSUE.md",
                  "SURVEY.md", "PERF_LEDGER.jsonl",
                  "tests/unit/test_repo_lint.py"}


def _checkout_files():
    """Every file of the checkout, found by walking it (no call to git):
    the directories .gitignore names and .git are not entered."""
    ignored = {ln.strip().rstrip("/") for ln in
               (REPO / ".gitignore").read_text().splitlines()
               if ln.strip().endswith("/")} | {".git"}
    for root, dirs, files in os.walk(REPO):
        dirs[:] = sorted(d for d in dirs if d not in ignored)
        yield from (Path(root) / name for name in sorted(files))


def test_nothing_names_a_deleted_script():
    """No source, test, workflow or document sends a reader to a file
    that is gone."""
    hits = []
    for path in _checkout_files():
        rel = path.relative_to(REPO).as_posix()
        if rel in _MAY_NAME_THEM or re.fullmatch(
                r"docs/ROUND\w*_NOTES\.md", rel):
            continue
        try:
            lines = path.read_text().splitlines()
        except UnicodeDecodeError:  # not a text file
            continue
        hits += [f"{rel}:{n}: {line.strip()[:100]}"
                 for n, line in enumerate(lines, 1)
                 if _DELETED_SCRIPTS.search(line)]
    assert hits == [], "\n  ".join(["names a deleted script:"] + hits)
