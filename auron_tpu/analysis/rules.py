"""The graftlint rule set: the runtime's cross-cutting contracts as AST
checks. Each rule encodes ONE invariant a past PR established and a
future PR could silently break; ANALYSIS.md documents the contracts in
prose. Scoping, heuristics and their limits are deliberate — every rule
errs toward *candidate* findings that the baseline freezes, never
toward silently passing a new violation of the real contract.
"""

from __future__ import annotations

import ast
import re
from typing import Iterable, Optional

from auron_tpu.analysis.core import FileContext, Project, Rule, rule

# directory scopes (repo-relative prefixes)
_RUNTIME_DIRS = ("auron_tpu/ops/", "auron_tpu/runtime/",
                 "auron_tpu/parallel/")
_TAXONOMY_DIRS = ("auron_tpu/runtime/", "auron_tpu/ops/",
                  "auron_tpu/fleet/")
_OPERATOR_DIRS = ("auron_tpu/ops/", "auron_tpu/parallel/",
                  "auron_tpu/io/", "auron_tpu/runtime/")


def _dotted(node: ast.AST) -> str:
    """``a.b.c`` for Name/Attribute chains, '' otherwise."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _contains_call(node: ast.AST, suffixes: tuple) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            d = _dotted(n.func)
            if d and (d.split(".")[-1] in suffixes):
                return True
    return False


# ---------------------------------------------------------------------------
# GL001 — sync discipline (PR 8's attribution invariant)
# ---------------------------------------------------------------------------

#: the sanctioned sync wrappers (obs/profile.py): waits routed through
#: them are credited as device time
_SANCTIONED = ("timed_get", "device_fence", "row_count")

#: call roots that mark a host-side value (skipped as candidates)
_HOST_FUNCS = frozenset((
    "len", "round", "min", "max", "sum", "abs", "ord", "hash", "id",
    "str", "repr", "int", "float", "bool", "divmod", "pow", "sorted",
    "time", "os", "math", "zlib", "json", "enumerate", "range",
))


@rule
class SyncDiscipline(Rule):
    """Device syncs in the execution packages must route through the
    profiler's sanctioned frames. PR 8 moved every per-batch sync to
    semantic boundaries (``profile.device_fence`` at materialization,
    ``profile.timed_get`` for control-scalar readbacks): a raw
    ``block_until_ready`` / ``jax.device_get`` / host conversion of a
    jax value both SERIALIZES the pipelined overlap and books the
    device wait into the wrong host bucket, so attribution stops
    summing to wall honestly. ``float()``/``int()``/``np.asarray`` on
    non-obviously-host values are reported as CANDIDATES (the baseline
    freezes today's ~230; a new one must justify itself)."""

    rule_id = "GL001"
    title = "sync-discipline"
    hint = ("route the readback through profile.timed_get(...) inside "
            "the operator's timer frame (a batch's row count through "
            "profile.row_count(batch)), or fence the semantic "
            "boundary with profile.device_fence(...); a provably "
            "host-only conversion may carry "
            "'# graft: disable=GL001 -- <why it is host-side>'")
    node_types = (ast.Attribute, ast.Call)
    dirs = _RUNTIME_DIRS

    def visit(self, node, ctx: FileContext) -> Iterable:
        if isinstance(node, ast.Attribute):
            if node.attr == "block_until_ready":
                yield self.violation(
                    ctx, node,
                    "raw block_until_ready outside a sanctioned "
                    "profile frame (PR 8 moved per-batch syncs to "
                    "device_fence/timed_get boundaries)")
            elif node.attr == "addressable_shards":
                yield self.violation(
                    ctx, node,
                    ".addressable_shards slices device state on the "
                    "host path — a hidden sync and a multihost "
                    "routing hazard (the reducer read path must stay "
                    "host-local or go through the RSS tier)")
            return
        # Calls
        func = node.func
        d = _dotted(func)
        leaf = d.split(".")[-1] if d else ""
        if leaf == "device_get":
            yield self.violation(
                ctx, node,
                "raw jax.device_get readback — the wait it absorbs "
                "books as host time; use profile.timed_get so the "
                "sync is credited as device wait")
            return
        if isinstance(func, ast.Name) and func.id in ("float", "int"):
            if len(node.args) != 1 or node.keywords:
                return
            arg = node.args[0]
            if self._host_side(arg):
                return
            yield self.violation(
                ctx, node,
                f"{func.id}() on a possibly device-resident value is "
                f"an implicit sync (candidate site)")
            return
        if leaf == "asarray" and d.split(".")[0] in ("np", "numpy"):
            if not node.args or self._host_side(node.args[0]):
                return
            yield self.violation(
                ctx, node,
                "np.asarray() on a possibly device-resident value is "
                "an implicit transfer+sync (candidate site)")

    @staticmethod
    def _host_side(arg: ast.AST) -> bool:
        """Conservatively true when the converted value is clearly a
        host value (literal, host-builtin result) or already routed
        through a sanctioned wrapper."""
        if isinstance(arg, (ast.Constant, ast.JoinedStr)):
            return True
        if _contains_call(arg, _SANCTIONED):
            return True
        if isinstance(arg, ast.Call):
            d = _dotted(arg.func)
            if d and (d.split(".")[0] in _HOST_FUNCS
                      or d.split(".")[-1] in _HOST_FUNCS):
                return True
        if isinstance(arg, ast.BinOp):
            return SyncDiscipline._host_side(arg.left) \
                and SyncDiscipline._host_side(arg.right)
        return False


# ---------------------------------------------------------------------------
# GL002 — donation safety (PR 3/10's retry-reuse contract)
# ---------------------------------------------------------------------------

@rule
class DonationSafety(Rule):
    """Buffer donation destroys its inputs, so every donation site must
    carry an explicit safety annotation: hashtable overflow retries
    re-run the step kernel on the SAME state+batch (PR 3), and the mesh
    exchange's quota escalation re-runs the stage program on the SAME
    inputs (PR 10) — donating there corrupts the retry. The annotation
    ``# graft: donation-ok -- <why the inputs are dead>`` (same line or
    the line above) states the argument; a site without one fails."""

    rule_id = "GL002"
    title = "donation-safety"
    hint = ("state why the donated inputs cannot be reused by any "
            "retry/escalation path with '# graft: donation-ok -- "
            "<reason>' on (or directly above) the call — or pass "
            "donate=False where a retry reuses inputs")
    node_types = (ast.Call,)

    def visit(self, node, ctx: FileContext) -> Iterable:
        for kw in node.keywords:
            if kw.arg not in ("donate", "donate_argnums"):
                continue
            # explicit non-donation is always safe
            v = kw.value
            if isinstance(v, ast.Constant) and not v.value:
                continue
            if isinstance(v, ast.Tuple) and not v.elts:
                continue
            if ctx.annotated("donation-ok", node.lineno):
                continue
            yield self.violation(
                ctx, node,
                f"donation site ({kw.arg}=...) without a "
                f"'# graft: donation-ok' annotation — overflow/"
                f"escalation retries that reuse inputs forbid "
                f"donation")
            return


# ---------------------------------------------------------------------------
# GL003 — trace-semantic knobs (PR 3's program-cache-key contract)
# ---------------------------------------------------------------------------

def _config_vocab():
    from auron_tpu import config as cfg
    keys = {o.key for o in cfg.options()}
    const_to_key = {}
    for name in dir(cfg):
        if not name.isupper():
            continue
        val = getattr(cfg, name)
        if isinstance(val, str) and val in keys:
            const_to_key[name] = val
    return keys, const_to_key, set(cfg.TRACE_SEMANTIC_KEYS)


_BUILDER_NAME = re.compile(r"(^build_kernel_fragment$|_kernel|_program"
                           r"|fragment)")


@rule
class TraceSemanticKnob(Rule):
    """A config knob read INSIDE kernel-builder code changes what the
    compiled program computes, so its value must ride every
    program-cache key — ``config.TRACE_SEMANTIC_KEYS`` feeds
    ``trace_salt()`` into runtime/programs.py for exactly this reason
    (the map-key-dedup precedent, PR 3). A knob read in a builder that
    is neither trace-semantic nor declared inert can serve a STALE
    compiled kernel after the knob flips."""

    rule_id = "GL003"
    title = "trace-semantic-knob"
    hint = ("add the key to config.TRACE_SEMANTIC_KEYS (it changes "
            "traced computation) or declare it inert with "
            "'# graft: inert-knob -- <why the traced program does not "
            "depend on it>'")
    node_types = (ast.Call,)

    def __init__(self):
        self._vocab = None

    def visit(self, node, ctx: FileContext) -> Iterable:
        if not (isinstance(node.func, ast.Attribute)
                and node.func.attr == "get" and node.args):
            return
        arg = node.args[0]
        key = None
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str) \
                and arg.value.startswith("auron."):
            key = arg.value
        elif isinstance(arg, ast.Attribute) or isinstance(arg, ast.Name):
            if self._vocab is None:
                self._vocab = _config_vocab()
            _, const_to_key, _ = self._vocab
            name = arg.attr if isinstance(arg, ast.Attribute) else arg.id
            key = const_to_key.get(name)
        if key is None:
            return
        fn = ctx.enclosing_function(node)
        if fn is None or not _BUILDER_NAME.search(fn.name):
            return
        if self._vocab is None:
            self._vocab = _config_vocab()
        _, _, salt_keys = self._vocab
        if key in salt_keys:
            return
        if ctx.annotated("inert-knob", node.lineno):
            return
        yield self.violation(
            ctx, node,
            f"config read of {key!r} inside kernel-builder "
            f"{fn.name!r} is not in config.TRACE_SEMANTIC_KEYS and "
            f"not declared inert — a flipped knob could serve a "
            f"stale compiled program")


# ---------------------------------------------------------------------------
# GL004 — error taxonomy (PR 4's classified-recovery contract)
# ---------------------------------------------------------------------------

@rule
class ErrorTaxonomy(Rule):
    """Runtime-path raises must be classified ``AuronError``s: the
    retry driver routes purely on ``errors.is_transient`` (PR 4 deleted
    the message-matching), so a bare ``raise RuntimeError`` gets the
    conservative default-retry treatment — retries+1 full recomputes of
    a deterministic failure — and a broad ``except Exception: pass``
    swallows classified verdicts the recovery plane needed to see."""

    rule_id = "GL004"
    title = "error-taxonomy"
    hint = ("raise a classified errors.AuronError subclass (double-"
            "inherit the builtin when legacy 'except' sites must keep "
            "working, the errors.py idiom); for a deliberate "
            "best-effort swallow, log or add '# graft: disable=GL004 "
            "-- <why swallowing is safe>'")
    node_types = (ast.Raise, ast.ExceptHandler)
    dirs = _TAXONOMY_DIRS

    def visit(self, node, ctx: FileContext) -> Iterable:
        if isinstance(node, ast.Raise):
            exc = node.exc
            name = ""
            if isinstance(exc, ast.Call):
                name = _dotted(exc.func)
            elif exc is not None:
                name = _dotted(exc)
            if name in ("RuntimeError", "Exception"):
                yield self.violation(
                    ctx, node,
                    f"bare 'raise {name}' in a runtime path — the "
                    f"retry driver routes on the errors.py taxonomy, "
                    f"not messages, and will blind-retry this")
            return
        # ExceptHandler: broad catch that silently swallows
        t = node.type
        broad = t is None or (isinstance(t, ast.Name)
                              and t.id in ("Exception", "BaseException"))
        if not broad:
            return
        body = node.body
        if all(isinstance(s, (ast.Pass, ast.Continue)) for s in body):
            yield self.violation(
                ctx, node,
                "broad 'except Exception' with a silent body swallows "
                "classified errors the recovery plane routes on")


# ---------------------------------------------------------------------------
# GL005 — knob-registry drift (config.py ↔ CONFIG.md ↔ use sites)
# ---------------------------------------------------------------------------

_CONFIG_MD_KEY = re.compile(r"^\|\s*`(auron\.[a-z0-9_.]+)`")


@rule
class KnobRegistryDrift(Rule):
    """Three-way consistency of the knob surface: every ``auron.*`` key
    read anywhere must be declared in config.py (an unknown key raises
    KeyError at runtime — at the user, not at CI); every declared key
    must appear in CONFIG.md and vice versa (the doc is generated —
    drift means someone hand-edited it or forgot to regenerate); and a
    declared knob nothing reads is a lie to the user (config.py's own
    declaration discipline)."""

    rule_id = "GL005"
    title = "knob-registry-drift"
    hint = ("declare new keys via config._opt, regenerate CONFIG.md "
            "(python -c \"from auron_tpu import config; "
            "open('CONFIG.md','w').write(config.generate_docs())\"), "
            "and delete knobs nothing reads")
    node_types = (ast.Call, ast.Attribute, ast.Name)

    def __init__(self):
        #: literal "auron.*" keys passed to .get/.set/.unset:
        #: [(rel, line, key)]
        self._literal_reads: list = []
        #: config-module constant names referenced outside config.py
        self._used_consts: set = set()
        #: literal keys seen ANYWHERE (string mention counts as a use
        #: for dead-knob purposes — tools reach knobs via env strings)
        self._literal_keys: set = set()

    def visit(self, node, ctx: FileContext) -> Iterable:
        in_config = ctx.rel == "auron_tpu/config.py"
        if isinstance(node, ast.Name):
            if not in_config and node.id.isupper():
                self._used_consts.add(node.id)
            return ()
        if isinstance(node, ast.Attribute):
            if not in_config and node.attr.isupper():
                self._used_consts.add(node.attr)
            return ()
        # Call: collect literal key reads through config-ish accessors
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in ("get", "set", "unset") \
                and node.args:
            a = node.args[0]
            if isinstance(a, ast.Constant) and isinstance(a.value, str) \
                    and a.value.startswith("auron."):
                self._literal_keys.add(a.value)
                if not in_config:
                    self._literal_reads.append(
                        (ctx.rel, node.lineno, a.value, ctx))
        return ()

    def finalize(self, project: Project) -> Iterable:
        import os

        from auron_tpu import config as cfg
        keys = {o.key for o in cfg.options()}
        _, const_to_key, _ = _config_vocab()
        key_to_const = {v: k for k, v in const_to_key.items()}

        # (a) literal reads of unknown keys
        for rel, line, key, ctx in self._literal_reads:
            if key not in keys:
                yield self.violation(
                    ctx, line,
                    f"config access of {key!r}, which is not declared "
                    f"in auron_tpu/config.py (KeyError at runtime)")

        # (b) config.py ↔ CONFIG.md key sets
        md_path = os.path.join(project.root, "CONFIG.md")
        md_keys: dict[str, int] = {}
        if os.path.exists(md_path):
            with open(md_path, encoding="utf-8") as f:
                for i, text in enumerate(f, start=1):
                    m = _CONFIG_MD_KEY.match(text)
                    if m:
                        md_keys[m.group(1)] = i
            for key in sorted(keys - set(md_keys)):
                yield Violation_md(
                    self, "CONFIG.md", 1,
                    f"declared knob {key!r} is missing from CONFIG.md "
                    f"— regenerate the doc")
            for key, line in sorted(md_keys.items()):
                if key not in keys:
                    yield Violation_md(
                        self, "CONFIG.md", line,
                        f"CONFIG.md documents {key!r}, which "
                        f"config.py no longer declares — regenerate "
                        f"the doc")
            if set(md_keys) == keys:
                # key sets agree: still fail on stale TEXT (a default
                # or doc string changed without regeneration)
                with open(md_path, encoding="utf-8") as f:
                    current = f.read()
                if current != cfg.generate_docs():
                    yield Violation_md(
                        self, "CONFIG.md", 1,
                        "CONFIG.md text differs from config."
                        "generate_docs() — a default or doc string "
                        "changed without regenerating")
        else:
            yield Violation_md(self, "CONFIG.md", 1,
                               "CONFIG.md is missing — regenerate it")

        # (c) dead knobs: declared but never referenced (by constant
        # name outside config.py, or by literal key anywhere)
        cfg_ctx = project.contexts.get("auron_tpu/config.py")
        if cfg_ctx is not None:
            for key in sorted(keys):
                const = key_to_const.get(key)
                if const and const in self._used_consts:
                    continue
                if key in self._literal_keys:
                    continue
                line = 1
                for i, text in enumerate(cfg_ctx.lines, start=1):
                    if f'"{key}"' in text:
                        line = i
                        break
                yield self.violation(
                    cfg_ctx, line,
                    f"declared knob {key!r} has no use site in the "
                    f"tree — an option nothing reads is a lie to the "
                    f"user (delete it, or land it with its feature)")


def Violation_md(r: Rule, file: str, line: int, message: str):
    """Violation on a non-Python surface (CONFIG.md has no AST ctx)."""
    from auron_tpu.analysis.core import Violation
    return Violation(file=file, line=line, rule=r.rule_id,
                     message=message, hint=r.hint, context="")


# ---------------------------------------------------------------------------
# GL006 — vocabulary drift (fault sites / trace categories)
# ---------------------------------------------------------------------------

_FAULT_FNS = frozenset(("maybe_fail", "maybe_hang", "maybe_cancel",
                        "maybe_corrupt", "fires"))
_TRACE_FNS = frozenset(("event", "complete_span", "category_enabled"))


@rule
class VocabularyDrift(Rule):
    """String literals at fault-plane and trace-plane call sites must
    belong to the documented vocabularies: an unknown fault site never
    fires (a chaos plan naming it is a silent no-op — faults.parse_plan
    validates plans, but the CODE side was unchecked), and an unknown
    trace category records events that ``auron.trace.events`` can never
    select and tools never aggregate."""

    rule_id = "GL006"
    title = "vocabulary-drift"
    hint = ("add the new site to runtime/faults.SITES (and its "
            "CONFIG.md doc) or the new category to obs/trace."
            "CATEGORIES before using it")
    node_types = (ast.Call,)

    def __init__(self):
        self._sites = self._kinds = self._cats = None

    def _load(self):
        if self._sites is None:
            from auron_tpu.obs import trace
            from auron_tpu.runtime import faults
            self._sites = set(faults.SITES)
            self._kinds = set(faults.KINDS)
            self._cats = set(trace.CATEGORIES)

    def visit(self, node, ctx: FileContext) -> Iterable:
        d = _dotted(node.func)
        if not d:
            return
        leaf = d.split(".")[-1]
        if leaf in _FAULT_FNS:
            # plain-named helpers ride on faults.* / direct import; a
            # same-named method on another object ("fires") must carry
            # a string that IS a site to be judged — non-literals skip
            if ctx.rel.endswith("runtime/faults.py"):
                return   # the plane's own implementation
            if not node.args:
                return
            a = node.args[0]
            if not (isinstance(a, ast.Constant)
                    and isinstance(a.value, str)):
                return
            self._load()
            # only judge dotted site-shaped strings when the callee is
            # not clearly the fault plane (avoids foreign .fires())
            base = d.split(".")[0]
            site_shaped = re.fullmatch(r"[a-z0-9_]+\.[a-z0-9_]+", a.value)
            if "fault" not in base and leaf == "fires" \
                    and not site_shaped:
                return
            if a.value not in self._sites:
                yield self.violation(
                    ctx, node,
                    f"fault site {a.value!r} is not in runtime/"
                    f"faults.SITES — it can never be armed by a "
                    f"chaos plan")
                return
            if leaf == "fires" and len(node.args) >= 2:
                k = node.args[1]
                if isinstance(k, ast.Constant) \
                        and isinstance(k.value, str) \
                        and k.value not in self._kinds:
                    yield self.violation(
                        ctx, node,
                        f"fault kind {k.value!r} is not in runtime/"
                        f"faults.KINDS")
            return
        if leaf in _TRACE_FNS:
            base = d.split(".")[0]
            if "trace" not in base:
                return   # threading.Event etc. — not the trace plane
            if ctx.rel.endswith("obs/trace.py"):
                return
            if not node.args:
                return
            a = node.args[0]
            if isinstance(a, ast.Constant) and isinstance(a.value, str):
                self._load()
                if a.value not in self._cats:
                    yield self.violation(
                        ctx, node,
                        f"trace category {a.value!r} is not in obs/"
                        f"trace.CATEGORIES — auron.trace.events can "
                        f"never select it and reports never "
                        f"aggregate it")


# ---------------------------------------------------------------------------
# GL007 — checkpoint coverage (PR 7's cooperative-lifecycle contract)
# ---------------------------------------------------------------------------

@rule
class CheckpointCoverage(Rule):
    """A batch-drive loop with no cooperative poll is invisible to the
    lifecycle plane: cancels/deadlines land only at the NEXT poll site,
    the stall watchdog sees no heartbeat, and injected lifecycle chaos
    (cancel.race / task.hang) gets no traffic. Every loop that drives a
    child operator stream (``for ... in <expr containing .execute(...)>``)
    must lexically contain a ``ctx.checkpoint(...)`` or
    ``check_cancelled()`` poll. Lexical check only: a loop that polls
    through a helper earns a suppression with the helper named."""

    rule_id = "GL007"
    title = "checkpoint-coverage"
    hint = ("poll ctx.checkpoint('<site>') inside the drive loop "
            "(heartbeat + lifecycle faults + cancel in one call); if "
            "the poll happens inside a called helper, suppress with "
            "'# graft: disable=GL007 -- polls via <helper>'")
    node_types = (ast.For,)
    dirs = _OPERATOR_DIRS

    def visit(self, node: ast.For, ctx: FileContext) -> Iterable:
        drives = any(
            isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute)
            and n.func.attr == "execute"
            for n in ast.walk(node.iter))
        if not drives:
            return
        for stmt in node.body:
            for n in ast.walk(stmt):
                if isinstance(n, ast.Call) \
                        and isinstance(n.func, ast.Attribute) \
                        and n.func.attr in ("checkpoint",
                                            "check_cancelled"):
                    return
        yield self.violation(
            ctx, node,
            "batch-drive loop over a child .execute() stream with no "
            "ctx.checkpoint / check_cancelled poll site — cancels, "
            "deadlines and the stall watchdog cannot land here")


# ---------------------------------------------------------------------------
# GL008 — lock order (static deadlock detector for PR 9–14 concurrency)
# ---------------------------------------------------------------------------

_LOCKISH = re.compile(r"(lock|cond|mutex)", re.IGNORECASE)


@rule
class LockOrder(Rule):
    """The concurrency added since PR 9 (scheduler slots, memmgr
    accounting, program registry, journal appender, ops-server
    refcount) acquires locks through ``with`` statements. This rule
    builds the lexical acquisition graph — an edge A→B whenever a
    ``with`` holding lock A contains a ``with`` acquiring lock B — and
    fails on cycles: two code paths acquiring the same pair of locks in
    opposite orders is the canonical deadlock, and it is invisible to
    every test that doesn't hit the exact interleaving. Lock names are
    qualified by class (``QueryScheduler._cond``) or module; same-named
    locks on DIFFERENT classes are distinct nodes."""

    rule_id = "GL008"
    title = "lock-order"
    hint = ("acquire the two locks in one global order everywhere "
            "(document it where both are declared), or restructure so "
            "one side releases before taking the other")
    node_types = ()   # own traversal (needs the nesting stack)

    def __init__(self):
        #: directed edges {(a, b): (rel, line)} — first site wins
        self._edges: dict = {}

    def begin_file(self, ctx: FileContext) -> None:
        self._class_stack: list[str] = []
        self._walk(ctx.tree, [], ctx)

    def _lock_name(self, expr: ast.AST, ctx: FileContext) -> Optional[str]:
        try:
            text = ast.unparse(expr)
        except Exception:   # pragma: no cover - malformed expr
            return None
        if not _LOCKISH.search(text):
            return None
        # qualify: self._lock → <Class>._lock; module globals → module
        cls = self._class_stack[-1] if self._class_stack else None
        if text.startswith("self.") and cls:
            return f"{cls}.{text[5:]}"
        if "." not in text:
            mod = ctx.rel.rsplit("/", 1)[-1].removesuffix(".py")
            return f"{mod}:{text}"
        return text

    def _walk(self, node: ast.AST, held: list, ctx: FileContext) -> None:
        if isinstance(node, ast.ClassDef):
            self._class_stack.append(node.name)
            for child in ast.iter_child_nodes(node):
                self._walk(child, held, ctx)
            self._class_stack.pop()
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a fresh frame: locks held lexically OUTSIDE a def are not
            # held when the def later runs
            for child in ast.iter_child_nodes(node):
                self._walk(child, [], ctx)
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            acquired = []
            for item in node.items:
                name = self._lock_name(item.context_expr, ctx)
                if name:
                    for h in held:
                        if h != name:
                            self._edges.setdefault(
                                (h, name), (ctx.rel, node.lineno))
                    acquired.append(name)
                    held = held + [name]
            for child in node.body:
                self._walk(child, held, ctx)
            return
        for child in ast.iter_child_nodes(node):
            self._walk(child, held, ctx)

    def finalize(self, project: Project) -> Iterable:
        graph: dict[str, list[str]] = {}
        for (a, b) in self._edges:
            graph.setdefault(a, []).append(b)
        # iterative three-color DFS; report each back edge's cycle once
        seen_cycles: set = set()
        color: dict[str, int] = {}   # 1 = on stack, 2 = done
        for start in sorted(graph):
            if color.get(start):
                continue
            stack = [(start, iter(graph.get(start, ())))]
            color[start] = 1
            path = [start]
            while stack:
                node, it = stack[-1]
                advanced = False
                for nxt in it:
                    if color.get(nxt) == 1:
                        i = path.index(nxt)
                        cycle = tuple(path[i:] + [nxt])
                        key = frozenset(cycle)
                        if key not in seen_cycles:
                            seen_cycles.add(key)
                            rel, line = self._edges[(node, nxt)]
                            from auron_tpu.analysis.core import Violation
                            yield Violation(
                                file=rel, line=line, rule=self.rule_id,
                                message=(
                                    "lock-order cycle: "
                                    + " -> ".join(cycle)
                                    + " — opposite-order acquisition "
                                      "is a latent deadlock"),
                                hint=self.hint, context="")
                    elif not color.get(nxt):
                        color[nxt] = 1
                        stack.append((nxt, iter(graph.get(nxt, ()))))
                        path.append(nxt)
                        advanced = True
                        break
                if not advanced:
                    color[node] = 2
                    stack.pop()
                    path.pop()
