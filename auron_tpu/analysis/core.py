"""graftlint framework: rule registry, per-file visitor multiplexing,
suppressions and the frozen-violation baseline.

Design (the shape ANALYSIS.md documents):

- **One parse per file.** Every rule declares the AST node types it
  wants (``node_types``); the analyzer parses each file once, annotates
  parent links, and multiplexes each node to the rules registered for
  its type. Project-level rules (knob drift, lock order) accumulate
  state per file and emit from ``finalize``.
- **Structured violations.** Each :class:`Violation` carries
  ``file:line``, the rule id, a message, a fix hint, and ``context`` —
  the stripped source line, which is the violation's BASELINE IDENTITY:
  baselines key on ``(file, rule, context)`` so entries survive
  unrelated line-number drift but die with the offending code.
- **Suppression grammar.** ``# graft: disable=<rule-id>[,<id>...] --
  <reason>`` on the offending line suppresses those rules there;
  ``# graft: disable-file=<rule-id> -- <reason>`` anywhere in the file
  suppresses for the whole file. The reason is MANDATORY — a disable
  without one (or naming an unknown rule) is itself a violation
  (:data:`META_RULE` GL000), so every grandfathered exception carries
  its justification in the tree.
- **Frozen baseline.** ``tools/lint_baseline.json`` records today's
  grandfathered violations; the gate fails only on violations NOT in
  the baseline, so the checker could land with ~200 pre-existing
  candidate sites without a flag day while every NEW violation fails
  the PR that introduces it. ``--update-baseline`` regenerates it;
  stale entries (baselined code that no longer violates) are reported
  so the baseline shrinks monotonically.
"""

from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass, field
from typing import Iterable, Iterator, Optional

#: rule id of the suppression-grammar meta rule (malformed/unknown
#: disables). Not suppressible — a broken suppression cannot excuse
#: itself.
META_RULE = "GL000"

#: the documented rule vocabulary (rules register themselves into this
#: at import; META_RULE is the framework's own)
_RULES: dict[str, type] = {}


def rule(cls):
    """Class decorator registering a rule by its ``rule_id``."""
    rid = cls.rule_id
    assert re.fullmatch(r"GL\d{3}", rid), f"bad rule id {rid!r}"
    assert rid not in _RULES, f"duplicate rule {rid}"
    _RULES[rid] = cls
    return cls


def all_rules() -> dict[str, type]:
    """{rule_id: rule class} — importing the rules module on demand so
    ``import auron_tpu.analysis`` stays cheap."""
    from auron_tpu.analysis import rules as _rules  # noqa: F401
    return dict(_RULES)


def known_rule_ids() -> set[str]:
    return set(all_rules()) | {META_RULE}


@dataclass(frozen=True)
class Violation:
    """One contract violation at ``file:line``."""

    file: str          # repo-relative posix path
    line: int
    rule: str          # GLnnn
    message: str
    hint: str = ""     # how to fix (the rule's standing advice)
    context: str = ""  # stripped source line — the baseline identity

    def key(self) -> tuple[str, str, str]:
        return (self.file, self.rule, self.context)

    def render(self) -> str:
        s = f"{self.file}:{self.line}: {self.rule}: {self.message}"
        if self.hint:
            s += f"\n    fix: {self.hint}"
        return s

    def to_json(self) -> dict:
        return {"file": self.file, "line": self.line, "rule": self.rule,
                "message": self.message, "hint": self.hint,
                "context": self.context}


class Rule:
    """Base rule. Subclasses set the class attributes and implement any
    of ``visit`` (per registered node), ``end_file`` (per file) and
    ``finalize`` (once, after every file) — each returns an iterable of
    :class:`Violation`. One instance lives per analysis run, so rules
    may accumulate cross-file state on ``self``."""

    rule_id: str = ""
    title: str = ""
    hint: str = ""
    #: AST node classes routed to ``visit`` (empty = none)
    node_types: tuple = ()
    #: repo-relative directory prefixes this rule applies to
    #: (None = every analyzed file)
    dirs: Optional[tuple] = None

    def applies(self, ctx: "FileContext") -> bool:
        if self.dirs is None:
            return True
        return any(ctx.rel.startswith(d) for d in self.dirs)

    def begin_file(self, ctx: "FileContext") -> None:
        pass

    def visit(self, node: ast.AST,
              ctx: "FileContext") -> Iterable[Violation]:
        return ()

    def end_file(self, ctx: "FileContext") -> Iterable[Violation]:
        return ()

    def finalize(self, project: "Project") -> Iterable[Violation]:
        return ()

    # -- helpers shared by rules ------------------------------------

    def violation(self, ctx: "FileContext", node_or_line,
                  message: str, hint: Optional[str] = None) -> Violation:
        line = getattr(node_or_line, "lineno", node_or_line)
        return Violation(
            file=ctx.rel, line=int(line), rule=self.rule_id,
            message=message,
            hint=self.hint if hint is None else hint,
            context=ctx.line_text(int(line)))


# ---------------------------------------------------------------------------
# suppression / annotation grammar
# ---------------------------------------------------------------------------

#: comment grammar: ``graft: disable=GL001[,GL004] -- reason`` (same
#: line) and ``graft: disable-file=GL007 -- reason`` (whole file),
#: each introduced by a hash
_SUPPRESS_RE = re.compile(
    r"#\s*graft:\s*(disable|disable-file)\s*=\s*"
    r"(?P<ids>[A-Za-z0-9_,\s]+?)"
    r"(?:\s*--\s*(?P<reason>.*))?$")

#: ``# graft: donation-ok -- reason`` / ``# graft: inert-knob -- reason``
#: — positive annotations rules consult (GL002/GL003); the reason is
#: mandatory like the disable grammar's.
_ANNOTATION_RE = re.compile(
    r"#\s*graft:\s*(?P<tag>donation-ok|inert-knob)\s*"
    r"(?:--\s*(?P<reason>.*))?$")


@dataclass
class _Suppressions:
    by_line: dict = field(default_factory=dict)      # line -> set(rule ids)
    file_wide: set = field(default_factory=set)      # rule ids
    annotations: dict = field(default_factory=dict)  # line -> set(tags)
    #: (line, message) pairs for malformed grammar → GL000
    malformed: list = field(default_factory=list)
    #: how many violations each suppression absorbed (the audit trail
    #: tools/lint_report.py prints) — keys (line, rule) / ("file", rule)
    used: dict = field(default_factory=dict)
    #: every well-formed disable directive as written:
    #: {line, scope: "line"|"file", rules: [..], reason}
    directives: list = field(default_factory=list)


def _comments(source: str) -> dict[int, str]:
    """{line: comment text} from real COMMENT tokens only — a
    ``# graft:`` inside a string literal or docstring is prose about
    the grammar, not a directive."""
    import io
    import tokenize
    out: dict[int, str] = {}
    try:
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                out[tok.start[0]] = tok.string
    except (tokenize.TokenError, IndentationError,
            SyntaxError):   # pragma: no cover - half-written file
        pass
    return out


def _parse_suppressions(source: str, known: set[str]) -> _Suppressions:
    sup = _Suppressions()
    for i, text in sorted(_comments(source).items()):
        if "graft:" not in text:
            continue
        m = _SUPPRESS_RE.search(text)
        if m:
            reason = (m.group("reason") or "").strip()
            ids = {s.strip() for s in m.group("ids").split(",") if s.strip()}
            if not reason:
                sup.malformed.append(
                    (i, "suppression without a reason — the grammar is "
                        "'# graft: disable=<rule-id> -- <reason>' and the "
                        "reason is mandatory"))
                continue
            unknown = sorted(ids - known)
            if unknown:
                sup.malformed.append(
                    (i, f"suppression names unknown rule id(s) "
                        f"{', '.join(unknown)}"))
                ids &= known
            if META_RULE in ids:
                sup.malformed.append(
                    (i, f"{META_RULE} (the suppression-grammar meta rule) "
                        f"cannot be suppressed"))
                ids.discard(META_RULE)
            if ids:
                sup.directives.append({
                    "line": i,
                    "scope": ("file" if m.group(1) == "disable-file"
                              else "line"),
                    "rules": sorted(ids), "reason": reason})
            if m.group(1) == "disable-file":
                sup.file_wide |= ids
            else:
                sup.by_line.setdefault(i, set()).update(ids)
            continue
        m = _ANNOTATION_RE.search(text)
        if m:
            reason = (m.group("reason") or "").strip()
            if not reason:
                sup.malformed.append(
                    (i, f"annotation '{m.group('tag')}' without a reason "
                        f"— '# graft: {m.group('tag')} -- <reason>'"))
                continue
            sup.annotations.setdefault(i, set()).add(m.group("tag"))
        elif re.search(r"#\s*graft:", text):
            sup.malformed.append(
                (i, "unrecognized '# graft:' directive (known: "
                    "disable=, disable-file=, donation-ok, inert-knob)"))
    return sup


# ---------------------------------------------------------------------------
# per-file context
# ---------------------------------------------------------------------------

class FileContext:
    """Everything the rules need about one parsed file."""

    def __init__(self, path: str, rel: str, source: str,
                 tree: ast.Module, known_rules: set[str]):
        self.path = path
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.tree = tree
        self.suppressions = _parse_suppressions(source, known_rules)
        # parent links (one pass; rules use them for enclosure queries)
        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                child._graft_parent = parent  # type: ignore[attr-defined]

    def line_text(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""

    def suppressed(self, rule_id: str, line: int) -> bool:
        """Directive lookup for a violation at ``line``: file-wide, the
        line itself, or a standalone directive in the contiguous
        comment block directly above — the same placement contract as
        ``annotated()``, so a long line's disable can sit above it."""
        sup = self.suppressions
        if rule_id in sup.file_wide:
            sup.used[("file", rule_id)] = \
                sup.used.get(("file", rule_id), 0) + 1
            return True
        i = line
        while i >= 1:
            if rule_id in sup.by_line.get(i, ()):
                sup.used[(i, rule_id)] = \
                    sup.used.get((i, rule_id), 0) + 1
                return True
            i -= 1
            if not self.line_text(i).startswith("#"):
                break
        return False

    def annotated(self, tag: str, line: int) -> bool:
        """Is annotation ``tag`` present on ``line`` or in the
        contiguous comment block directly above it? (The idiomatic spot
        is a comment above the call; wrapped reasons span lines.)"""
        ann = self.suppressions.annotations
        if tag in ann.get(line, ()):
            return True
        i = line - 1
        while i >= 1 and self.line_text(i).startswith("#"):
            if tag in ann.get(i, ()):
                return True
            i -= 1
        return False

    # -- AST enclosure helpers --------------------------------------

    def parents(self, node: ast.AST) -> Iterator[ast.AST]:
        while True:
            node = getattr(node, "_graft_parent", None)
            if node is None:
                return
            yield node

    def enclosing_function(self, node: ast.AST):
        for p in self.parents(node):
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return p
        return None

    def enclosing_class(self, node: ast.AST):
        for p in self.parents(node):
            if isinstance(p, ast.ClassDef):
                return p
        return None


# ---------------------------------------------------------------------------
# project: cross-file state for finalize-phase rules
# ---------------------------------------------------------------------------

class Project:
    """Carried through the run and handed to ``Rule.finalize``."""

    def __init__(self, root: str, files: list[str]):
        self.root = root
        self.files = files
        #: {rel: FileContext} — retained so finalize-phase violations
        #: still honor per-line suppressions in files that have one
        self.contexts: dict[str, FileContext] = {}

    def rel(self, path: str) -> str:
        return os.path.relpath(path, self.root).replace(os.sep, "/")


# ---------------------------------------------------------------------------
# file discovery
# ---------------------------------------------------------------------------

#: basenames / path fragments never analyzed (generated code, caches)
_EXCLUDE_PARTS = ("__pycache__",)
_EXCLUDE_FILES = ("auron_pb2.py",)


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def default_targets(root: Optional[str] = None) -> list[str]:
    """The analyzed tree: the package, the tools, and the top-level
    drivers. tests/ is deliberately excluded — fixtures seed violations
    on purpose; the gate lints the product, not its test fixtures."""
    root = root or repo_root()
    targets = [os.path.join(root, "auron_tpu"),
               os.path.join(root, "tools"),
               os.path.join(root, "bench.py"),
               os.path.join(root, "chip_smoke.py"),
               os.path.join(root, "__graft_entry__.py")]
    return [t for t in targets if os.path.exists(t)]


def iter_python_files(targets: Iterable[str]) -> list[str]:
    out = []
    for target in targets:
        if os.path.isfile(target):
            if target.endswith(".py"):
                out.append(target)
            continue
        for dirpath, dirnames, filenames in os.walk(target):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in _EXCLUDE_PARTS)
            for fn in sorted(filenames):
                if fn.endswith(".py") and fn not in _EXCLUDE_FILES:
                    out.append(os.path.join(dirpath, fn))
    return out


# ---------------------------------------------------------------------------
# the analyzer
# ---------------------------------------------------------------------------

@dataclass
class AnalysisResult:
    violations: list        # post-suppression
    suppressed: int         # count absorbed by disable directives
    files_scanned: int
    parse_errors: list      # (rel, message)
    #: every disable directive as written, with its absorption count:
    #: [{file, line, scope, rules, reason, used}] — the audit surface
    #: (a used=0 directive suppresses nothing and deserves a look)
    suppression_inventory: list = field(default_factory=list)

    def by_rule(self) -> dict[str, int]:
        counts: dict[str, int] = {}
        for v in self.violations:
            counts[v.rule] = counts.get(v.rule, 0) + 1
        return dict(sorted(counts.items()))

    def to_json(self) -> dict:
        return {
            "files_scanned": self.files_scanned,
            "violations": [v.to_json() for v in self.violations],
            "suppressed": self.suppressed,
            "by_rule": self.by_rule(),
            "parse_errors": list(self.parse_errors),
            "suppression_inventory": list(self.suppression_inventory),
        }


def analyze(targets: Optional[Iterable[str]] = None,
            root: Optional[str] = None,
            rule_ids: Optional[Iterable[str]] = None) -> AnalysisResult:
    """Run the checker over ``targets`` (default: the repo tree).

    ``rule_ids`` narrows to a subset (tests exercise rules in
    isolation). The tree parses ONCE per file; every selected rule sees
    the same walk."""
    root = root or repo_root()
    targets = list(targets) if targets is not None \
        else default_targets(root)
    files = iter_python_files(targets)
    classes = all_rules()
    if rule_ids is not None:
        wanted = set(rule_ids)
        classes = {rid: c for rid, c in classes.items() if rid in wanted}
    rules = [cls() for _, cls in sorted(classes.items())]
    known = known_rule_ids()
    project = Project(root, files)

    violations: list[Violation] = []
    suppressed = 0
    parse_errors: list[tuple] = []

    def admit(ctx: FileContext, vs: Iterable[Violation]) -> None:
        nonlocal suppressed
        for v in vs:
            if ctx.suppressed(v.rule, v.line):
                suppressed += 1
            else:
                violations.append(v)

    for path in files:
        rel = project.rel(path)
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
            tree = ast.parse(source, filename=rel)
        except (SyntaxError, UnicodeDecodeError, OSError) as e:
            parse_errors.append((rel, f"{type(e).__name__}: {e}"))
            continue
        ctx = FileContext(path, rel, source, tree, known)
        project.contexts[rel] = ctx
        # suppression-grammar meta rule (not itself suppressible)
        for line, msg in ctx.suppressions.malformed:
            violations.append(Violation(
                file=rel, line=line, rule=META_RULE, message=msg,
                hint="grammar: '# graft: disable=<rule-id> -- <reason>' "
                     "(reason mandatory)",
                context=ctx.line_text(line)))
        active = [r for r in rules if r.applies(ctx)]
        for r in active:
            r.begin_file(ctx)
        dispatch: dict[type, list] = {}
        for r in active:
            for t in r.node_types:
                dispatch.setdefault(t, []).append(r)
        for node in ast.walk(tree):
            for r in dispatch.get(type(node), ()):
                admit(ctx, r.visit(node, ctx))
        for r in active:
            admit(ctx, r.end_file(ctx))

    for r in rules:
        # finalize-phase violations honor line suppressions when they
        # land in an analyzed file (dead-knob findings on config.py
        # declarations); findings on non-Python surfaces (CONFIG.md)
        # have no suppression channel — fix the doc instead
        for v in r.finalize(project):
            fctx = project.contexts.get(v.file)
            if fctx is not None and fctx.suppressed(v.rule, v.line):
                suppressed += 1
            else:
                violations.append(v)

    inventory = []
    for rel, ctx in sorted(project.contexts.items()):
        sup = ctx.suppressions
        for d in sup.directives:
            if d["scope"] == "file":
                used = sum(sup.used.get(("file", r), 0)
                           for r in d["rules"])
            else:
                used = sum(sup.used.get((d["line"], r), 0)
                           for r in d["rules"])
            inventory.append({"file": rel, "line": d["line"],
                              "scope": d["scope"], "rules": d["rules"],
                              "reason": d["reason"], "used": used})

    violations.sort(key=lambda v: (v.file, v.line, v.rule))
    return AnalysisResult(violations, suppressed, len(files),
                          parse_errors, inventory)


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

BASELINE_VERSION = 1


def default_baseline_path(root: Optional[str] = None) -> str:
    return os.path.join(root or repo_root(), "tools",
                        "lint_baseline.json")


def load_baseline(path: str) -> dict:
    """Parse a baseline file; raises ValueError on a wrong schema (the
    gate must fail loudly on a garbage baseline, not pass vacuously)."""
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if not isinstance(data, dict) \
            or data.get("version") != BASELINE_VERSION \
            or not isinstance(data.get("entries"), list):
        raise ValueError(
            f"{path}: not a graftlint baseline "
            f"(want {{version: {BASELINE_VERSION}, entries: [...]}})")
    for e in data["entries"]:
        if not isinstance(e, dict) or "file" not in e or "rule" not in e:
            raise ValueError(f"{path}: malformed baseline entry {e!r}")
    return data


def save_baseline(path: str, violations: Iterable[Violation]) -> dict:
    """Freeze ``violations`` as the new baseline (sorted, counted by
    (file, rule, context) so unrelated line drift never dirties it)."""
    counts: dict[tuple, int] = {}
    for v in violations:
        counts[v.key()] = counts.get(v.key(), 0) + 1
    entries = [
        {"file": f, "rule": r, "context": c, "count": n}
        for (f, r, c), n in sorted(counts.items())]
    data = {"version": BASELINE_VERSION,
            "tool": "auron_tpu.analysis",
            "entries": entries}
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return data


def apply_baseline(violations: list, baseline: dict):
    """Split ``violations`` into (new, grandfathered) against the
    baseline, and report stale entries — frozen budget that matched
    nothing this run. A key frozen at count N whose sites were PARTLY
    fixed is stale too (``unmatched`` = leftover budget): leftover
    budget would silently grandfather future identical violations, so
    the report prompts pruning it with --update-baseline.

    Matching is by (file, rule, context) with per-key counts: a key
    frozen at count N absorbs at most N current violations, so ADDING
    an identical violation on a new line in the same file still fails
    the gate."""
    budget: dict[tuple, int] = {}
    for e in baseline.get("entries", ()):
        key = (e["file"], e["rule"], e.get("context", ""))
        budget[key] = budget.get(key, 0) + int(e.get("count", 1))
    new, grandfathered = [], []
    for v in violations:
        k = v.key()
        if budget.get(k, 0) > 0:
            budget[k] -= 1
            grandfathered.append(v)
        else:
            new.append(v)
    stale = [
        {"file": f, "rule": r, "context": c, "unmatched": n}
        for (f, r, c), n in sorted(budget.items()) if n > 0]
    return new, grandfathered, stale


def run(targets: Optional[Iterable[str]] = None,
        baseline_path: Optional[str] = None,
        root: Optional[str] = None) -> dict:
    """One-call gate for tests/tools: analyze, apply the baseline when
    given, and return the full machine-readable report."""
    result = analyze(targets, root=root)
    report = result.to_json()
    if baseline_path:
        baseline = load_baseline(baseline_path)
        new, old, stale = apply_baseline(result.violations, baseline)
        report["violations"] = [v.to_json() for v in new]
        report["new_violations"] = len(new)
        report["grandfathered"] = len(old)
        report["stale_baseline_entries"] = stale
    else:
        report["new_violations"] = len(result.violations)
        report["grandfathered"] = 0
        report["stale_baseline_entries"] = []
    report["ok"] = (report["new_violations"] == 0
                    and not report["parse_errors"])
    return report
