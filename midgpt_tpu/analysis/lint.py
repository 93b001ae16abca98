"""graftcheck pass 1: repo-specific AST lint. Deliberately JAX-free.

Every rule encodes a gotcha this repo has already paid for (rationale and
the citations live in docs/ANALYSIS.md):

  GC001  lax.cond / lax.while_loop / lax.fori_loop inside a Pallas kernel
         body (kills Mosaic pipelining — use straight-line selects).
  GC002  host materialization of traced values inside jit/scan/kernel
         scopes: float()/int() on non-constants, .item(), np.asarray/array.
  GC003  BlockSpec literal shapes whose last two dims are neither
         (8, 128)-divisible nor a plausible full-dim singleton.
  GC004  reading a donated argument after the donating call site.
  GC005  time.time()-style wall clock or np.random reachable from traced
         scopes (baked in at trace time — silently constant).
  GC006  function docstrings claiming parity without a `reference file:line`
         citation or a pinning-test citation (tests/...py).
  GC007  bare/broad `except` that swallows failures of checkpoint or
         collective call sites (a silently-dropped save/restore/collective
         is how runs lose state or deadlock half a mesh — robustness PR).
  GC008  bare `.astype(int8)` with no rounding in sight: the cast TRUNCATES
         toward zero, so float values quantized that way lose up to a full
         step of precision and bias toward 0 — quantization must round
         (ops/quant.py quantize_q8 is the blessed path; int8 KV cache PR).
  GC012  bare wall-clock CALL (`time.time()` / `time.perf_counter()` /
         `time.monotonic()` ...) in a `sampling/` or `robustness/` module:
         those hot paths measure latency through the injectable clock
         (`clock=` ctor param threaded to `self._clock`), which is what
         keeps round decomposition on ONE clock and lets tests fake
         time. Default-arg REFERENCES (`clock=time.perf_counter`) are the
         plumbing itself, not a read — only Call nodes are flagged, and
         `time.sleep()` is not a clock read (observability PR).

Scope model: a function is *traced* if it is jit-decorated (including
`functools.partial(jax.jit, ...)` and `name = jax.jit(fn)` rebinding), a
Pallas kernel (passed — possibly via functools.partial — to pallas_call),
or a named lax.scan body; plus, transitively, any same-module function it
calls by bare name. Lexically nested defs are analyzed as part of the
enclosing scope's subtree. Cross-module calls are not resolved — this is a
lint, not an interpreter; it trades soundness for zero false-positive noise
on idiomatic code.

Suppression: `# graftcheck: disable=GC001[,GC002] — one-line justification`
on the flagged line. The justification text is kept so the lint-clean gate
(tests/test_lint_clean.py) can reject bare, unexplained suppressions.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import os
import re
import tokenize
import typing as tp

RULES: tp.Dict[str, str] = {
    "GC001": "lax control flow inside a Pallas kernel body",
    "GC002": "host materialization of a traced value inside a traced scope",
    "GC003": "BlockSpec literal block shape violates the (8, 128) tiling rule",
    "GC004": "donated argument read after the donating call site",
    "GC005": "wall clock / numpy RNG reachable from a traced scope",
    "GC006": "parity claim without a reference or pinning-test citation",
    "GC007": "swallowed exception around a checkpoint/collective call site",
    "GC008": "truncating .astype(int8) cast — quantization must round",
    "GC012": "bare wall-clock call in a serving/robustness hot path",
}

# Default lint roots, relative to the repo root (tests are excluded on
# purpose: fixture snippets there *are* violations).
DEFAULT_LINT_ROOTS = ("midgpt_tpu", "tools", "launch.py", "sample.py")

_SUPPRESS_RE = re.compile(
    r"graftcheck:\s*disable=((?:GC\d{3})(?:\s*,\s*GC\d{3})*)\s*(.*)", re.DOTALL
)
_PARITY_RE = re.compile(r"\bparit(?:y|ies)\b", re.IGNORECASE)
_REFERENCE_CITE_RE = re.compile(r"\breference\s+[\w./\\-]+:\d+")
_TEST_CITE_RE = re.compile(r"\btests[/\\]\w+\.py\b")


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    col: int
    message: str

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"

    def to_dict(self) -> tp.Dict[str, tp.Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class Suppression:
    line: int
    rules: tp.Tuple[str, ...]
    justification: str


def parse_suppressions(source: str) -> tp.List[Suppression]:
    """All `# graftcheck: disable=...` comments with their line numbers."""
    out: tp.List[Suppression] = []
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if m:
                rules = tuple(r.strip() for r in m.group(1).split(","))
                out.append(Suppression(tok.start[0], rules, m.group(2).strip()))
    except tokenize.TokenError:
        pass  # syntax problems surface via ast.parse instead
    return out


# ----------------------------------------------------------------------
# AST helpers
# ----------------------------------------------------------------------


def _dotted(node: ast.AST) -> tp.Optional[str]:
    """'a.b.c' for a Name/Attribute chain rooted at a Name, else None."""
    parts: tp.List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _call_name(call: ast.Call) -> tp.Optional[str]:
    return _dotted(call.func)


def _is_jax_jit(node: ast.AST) -> bool:
    return _dotted(node) in ("jax.jit", "jit", "pjit", "jax.pjit")


def _partial_of(call: ast.Call) -> tp.Optional[ast.AST]:
    """The wrapped callable if `call` is functools.partial(fn, ...)."""
    if _call_name(call) in ("functools.partial", "partial") and call.args:
        return call.args[0]
    return None


def _unwrap_callable(node: ast.AST) -> tp.Optional[str]:
    """Bare name of a callable expr: Name, partial(Name, ...), or dotted."""
    if isinstance(node, ast.Call):
        inner = _partial_of(node)
        if inner is not None:
            return _unwrap_callable(inner)
        return None
    return _dotted(node)


_FuncDef = tp.Union[ast.FunctionDef, ast.AsyncFunctionDef]


class _Module:
    """One parsed module with the scope/donation index the rules share."""

    def __init__(self, path: str, source: str, tree: ast.Module):
        self.path = path
        self.source = source
        self.tree = tree
        self.parents: tp.Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                self.parents[child] = parent
        self.defs: tp.List[_FuncDef] = [
            n
            for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        self.defs_by_name: tp.Dict[str, tp.List[_FuncDef]] = {}
        for d in self.defs:
            self.defs_by_name.setdefault(d.name, []).append(d)
        # `kernel = functools.partial(_fwd_kernel, ...)` style indirection:
        # an alias map so pallas_call(kernel, ...) still resolves. Multi-
        # valued: the same variable may bind different kernels per branch.
        self.aliases: tp.Dict[str, tp.Set[str]] = {}
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
            ):
                target = _unwrap_callable(node.value)
                if target:
                    self.aliases.setdefault(node.targets[0].id, set()).add(target)
        self.kernel_defs = self._kernel_defs()
        self.traced_defs = self._traced_defs()
        self.donators = self._donators()

    # -- scope discovery ------------------------------------------------

    def resolve_defs(self, name: tp.Optional[str]) -> tp.List[_FuncDef]:
        """Defs a (dotted) callable name may refer to, following aliases."""
        if not name:
            return []
        out: tp.List[_FuncDef] = []
        seen: tp.Set[str] = set()
        frontier = [name]
        while frontier:
            leaf = frontier.pop().split(".")[-1]
            if leaf in seen:
                continue
            seen.add(leaf)
            if leaf in self.defs_by_name:
                out.extend(self.defs_by_name[leaf])
            else:
                frontier.extend(self.aliases.get(leaf, ()))
        return out

    def _jit_root_defs(self) -> tp.Set[_FuncDef]:
        roots: tp.Set[_FuncDef] = set()
        for d in self.defs:
            for deco in d.decorator_list:
                if _is_jax_jit(deco):
                    roots.add(d)
                elif isinstance(deco, ast.Call):
                    inner = _partial_of(deco)
                    if inner is not None and _is_jax_jit(inner):
                        roots.add(d)
                    elif _is_jax_jit(deco.func):
                        roots.add(d)
        # name = jax.jit(fn, ...) rebinding of a module function
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call) and _is_jax_jit(node.func) and node.args:
                for d in self.resolve_defs(_unwrap_callable(node.args[0])):
                    roots.add(d)
        return roots

    def _kernel_defs(self) -> tp.Set[_FuncDef]:
        """Functions used as Pallas kernel bodies (first arg of pallas_call)."""
        kernels: tp.Set[_FuncDef] = set()
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            if not name or name.split(".")[-1] != "pallas_call":
                continue
            args = list(node.args)
            for kw in node.keywords:
                if kw.arg == "kernel":
                    args.insert(0, kw.value)
            if not args:
                continue
            for d in self.resolve_defs(_unwrap_callable(args[0])):
                kernels.add(d)
        return self._closure(kernels)

    def _scan_body_defs(self) -> tp.Set[_FuncDef]:
        bodies: tp.Set[_FuncDef] = set()
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            if not name:
                continue
            leaf = name.split(".")[-1]
            if leaf not in ("scan", "while_loop", "fori_loop", "cond"):
                continue
            for arg in node.args:
                for d in self.resolve_defs(_unwrap_callable(arg)):
                    bodies.add(d)
        return bodies

    def _closure(self, roots: tp.Set[_FuncDef]) -> tp.Set[_FuncDef]:
        """roots plus same-module functions they call by bare name."""
        seen = set(roots)
        frontier = list(roots)
        while frontier:
            d = frontier.pop()
            for node in ast.walk(d):
                if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                    for callee in self.defs_by_name.get(node.func.id, []):
                        if callee not in seen:
                            seen.add(callee)
                            frontier.append(callee)
        return seen

    def _traced_defs(self) -> tp.Set[_FuncDef]:
        roots = self._jit_root_defs() | self.kernel_defs | self._scan_body_defs()
        return self._closure(roots)

    # -- donation index -------------------------------------------------

    def _donators(self) -> tp.Dict[str, tp.Tuple[_FuncDef, tp.Tuple[int, ...]]]:
        """name -> (def, donated positional indices) for this module."""
        out: tp.Dict[str, tp.Tuple[_FuncDef, tp.Tuple[int, ...]]] = {}

        def donated_from_call(call: ast.Call) -> tp.Tuple[int, ...]:
            for kw in call.keywords:
                if kw.arg == "donate_argnums":
                    v = kw.value
                    if isinstance(v, ast.Constant) and isinstance(v.value, int):
                        return (v.value,)
                    if isinstance(v, (ast.Tuple, ast.List)):
                        idx = [
                            e.value
                            for e in v.elts
                            if isinstance(e, ast.Constant) and isinstance(e.value, int)
                        ]
                        return tuple(idx)
            return ()

        for d in self.defs:
            for deco in d.decorator_list:
                if not isinstance(deco, ast.Call):
                    continue
                donated = donated_from_call(deco)
                if donated and (
                    _is_jax_jit(deco.func) or (_partial_of(deco) is not None and _is_jax_jit(_partial_of(deco)))
                ):
                    out[d.name] = (d, donated)
        # name = jax.jit(fn, donate_argnums=...) rebinding
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Assign) or not isinstance(node.value, ast.Call):
                continue
            call = node.value
            if not _is_jax_jit(call.func) or not call.args:
                continue
            donated = donated_from_call(call)
            target = _unwrap_callable(call.args[0])
            if donated and target:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        for d in self.resolve_defs(target):
                            out[tgt.id] = (d, donated)
        return out

    # -- generic lookups ------------------------------------------------

    def enclosing_stmt(self, node: ast.AST) -> ast.stmt:
        cur = node
        while not isinstance(cur, ast.stmt):
            cur = self.parents[cur]
        return cur

    def enclosing_function(self, node: ast.AST) -> tp.Optional[_FuncDef]:
        cur: tp.Optional[ast.AST] = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self.parents.get(cur)
        return None

    def enclosing_loop(
        self, node: ast.AST, within: tp.Optional[ast.AST] = None
    ) -> tp.Optional[ast.stmt]:
        cur: tp.Optional[ast.AST] = self.parents.get(node)
        while cur is not None and cur is not within:
            if isinstance(cur, (ast.For, ast.While, ast.AsyncFor)):
                return cur
            cur = self.parents.get(cur)
        return None


# ----------------------------------------------------------------------
# rules
# ----------------------------------------------------------------------


def _rule_gc001(mod: _Module) -> tp.Iterator[Finding]:
    targets = {"cond", "while_loop", "fori_loop"}
    for kern in mod.kernel_defs:
        for node in ast.walk(kern):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            if not name:
                continue
            parts = name.split(".")
            if parts[-1] in targets and (len(parts) == 1 or "lax" in parts[:-1]):
                yield Finding(
                    "GC001",
                    mod.path,
                    node.lineno,
                    node.col_offset,
                    f"`{name}` inside Pallas kernel `{kern.name}` defeats Mosaic "
                    "pipelining — use straight-line selects / pl.when "
                    "(CLAUDE.md Mosaic gotchas)",
                )


def _has_static_shape_arg(node: ast.AST) -> bool:
    """int()/float() of .shape/.ndim/.size/len() is static — not a sync."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in ("shape", "ndim", "size", "dtype", "itemsize", "nbytes"):
            return True
        if isinstance(sub, ast.Call) and _dotted(sub.func) == "len":
            return True
    return False


def _rule_gc002(mod: _Module) -> tp.Iterator[Finding]:
    for fn in mod.traced_defs:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            if name in ("float", "int", "bool", "complex"):
                if node.args and not any(
                    isinstance(a, ast.Constant) or _has_static_shape_arg(a)
                    for a in node.args
                ):
                    yield Finding(
                        "GC002",
                        mod.path,
                        node.lineno,
                        node.col_offset,
                        f"`{name}()` on a traced value inside `{fn.name}` forces a "
                        "host sync at trace time (ConcretizationTypeError or a "
                        "silent constant)",
                    )
            elif isinstance(node.func, ast.Attribute) and node.func.attr == "item":
                yield Finding(
                    "GC002",
                    mod.path,
                    node.lineno,
                    node.col_offset,
                    f"`.item()` inside traced `{fn.name}` is a device->host sync",
                )
            elif name in ("np.asarray", "numpy.asarray", "np.array", "numpy.array"):
                yield Finding(
                    "GC002",
                    mod.path,
                    node.lineno,
                    node.col_offset,
                    f"`{name}` inside traced `{fn.name}` materializes the traced "
                    "value on host (use jnp)",
                )


def _rule_gc003(mod: _Module) -> tp.Iterator[Finding]:
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        if not name or name.split(".")[-1] != "BlockSpec":
            continue
        shape: tp.Optional[ast.AST] = node.args[0] if node.args else None
        if shape is None:
            for kw in node.keywords:
                if kw.arg == "block_shape":
                    shape = kw.value
        if not isinstance(shape, (ast.Tuple, ast.List)) or len(shape.elts) < 2:
            continue
        last_two = shape.elts[-2:]
        if not all(
            isinstance(e, ast.Constant) and isinstance(e.value, int) for e in last_two
        ):
            continue  # symbolic dims: not statically checkable
        sublane, lane = (e.value for e in last_two)  # type: ignore[union-attr]
        # 1 is accepted as a plausible full singleton dim; anything else must
        # obey the (8, 128) tiling rule unless it spans the full array dim —
        # which a literal cannot prove, so suppress with justification if so.
        bad_sublane = sublane != 1 and sublane % 8 != 0
        bad_lane = lane != 1 and lane % 128 != 0
        if bad_sublane or bad_lane:
            yield Finding(
                "GC003",
                mod.path,
                node.lineno,
                node.col_offset,
                f"BlockSpec last-two dims ({sublane}, {lane}) are not "
                "(8, 128)-divisible; Mosaic requires divisibility or spanning "
                "the full array dim (CLAUDE.md) — suppress with justification "
                "if these span the array",
            )


def _stores_in(node: ast.AST) -> tp.Set[str]:
    """Dotted names assigned anywhere under `node`."""
    out: tp.Set[str] = set()
    for sub in ast.walk(node):
        if isinstance(sub, (ast.Name, ast.Attribute)) and isinstance(
            getattr(sub, "ctx", None), (ast.Store, ast.Del)
        ):
            d = _dotted(sub)
            if d:
                out.add(d)
    return out


def _rule_gc004(mod: _Module) -> tp.Iterator[Finding]:
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) or not isinstance(node.func, ast.Name):
            continue
        entry = mod.donators.get(node.func.id)
        if entry is None:
            continue
        fdef, donated = entry
        params = [a.arg for a in fdef.args.args]
        donated_exprs: tp.List[str] = []
        for idx in donated:
            expr: tp.Optional[ast.AST] = None
            if idx < len(node.args):
                expr = node.args[idx]
            elif idx < len(params):
                for kw in node.keywords:
                    if kw.arg == params[idx]:
                        expr = kw.value
            if expr is not None:
                d = _dotted(expr)
                if d:
                    donated_exprs.append(d)
        if not donated_exprs:
            continue
        stmt = mod.enclosing_stmt(node)
        scope: ast.AST = mod.enclosing_function(node) or mod.tree
        reassigned_here = _stores_in(stmt)
        end = getattr(stmt, "end_lineno", stmt.lineno)
        for expr in donated_exprs:
            if expr in reassigned_here:
                continue  # rebound by the donating statement itself
            # first later occurrence in the scope decides: Load -> stale read
            later: tp.List[tp.Tuple[int, int, bool]] = []
            for sub in ast.walk(scope):
                if isinstance(sub, (ast.Name, ast.Attribute)) and _dotted(sub) == expr:
                    if sub.lineno > end:
                        is_store = isinstance(sub.ctx, (ast.Store, ast.Del))
                        later.append((sub.lineno, sub.col_offset, is_store))
            later.sort()
            if later and not later[0][2]:
                yield Finding(
                    "GC004",
                    mod.path,
                    later[0][0],
                    later[0][1],
                    f"`{expr}` was donated to `{node.func.id}` at line "
                    f"{node.lineno} — its buffer is deleted; reading it here "
                    "raises (or silently aliases) at runtime",
                )
                continue
            loop = mod.enclosing_loop(stmt, within=scope)
            if loop is not None and expr not in _stores_in(loop):
                yield Finding(
                    "GC004",
                    mod.path,
                    node.lineno,
                    node.col_offset,
                    f"`{expr}` is donated to `{node.func.id}` inside a loop but "
                    "never rebound in the loop body — the next iteration reads "
                    "a deleted buffer",
                )


def _rule_gc005(mod: _Module) -> tp.Iterator[Finding]:
    clock_fns = {"time", "perf_counter", "monotonic", "process_time", "time_ns"}
    for fn in mod.traced_defs:
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                name = _call_name(node)
                if name and "." in name:
                    root, leaf = name.split(".")[0], name.split(".")[-1]
                    if root == "time" and leaf in clock_fns:
                        yield Finding(
                            "GC005",
                            mod.path,
                            node.lineno,
                            node.col_offset,
                            f"`{name}()` inside traced `{fn.name}` is evaluated "
                            "once at trace time — the compiled program sees a "
                            "frozen constant",
                        )
            if isinstance(node, ast.Attribute) and node.attr == "random":
                root = _dotted(node)
                if root in ("np.random", "numpy.random"):
                    yield Finding(
                        "GC005",
                        mod.path,
                        node.lineno,
                        node.col_offset,
                        f"`{root}` inside traced `{fn.name}`: host RNG is baked "
                        "in at trace time — use jax.random with a threaded key",
                    )


def _rule_gc006(mod: _Module) -> tp.Iterator[Finding]:
    for fn in mod.defs:
        doc = ast.get_docstring(fn, clean=False)
        if not doc or not _PARITY_RE.search(doc):
            continue
        if _REFERENCE_CITE_RE.search(doc) or _TEST_CITE_RE.search(doc):
            continue
        yield Finding(
            "GC006",
            mod.path,
            fn.lineno,
            fn.col_offset,
            f"docstring of `{fn.name}` claims parity but cites neither "
            "`reference file:line` nor a pinning test (CLAUDE.md convention)",
        )


# Leaf names of checkpoint-manager and cross-device/host collective calls:
# the operations whose failure must never be silently dropped (a swallowed
# save means lost state; a swallowed collective means half the mesh enters
# the op and deadlocks). Dotted calls only — bare local helpers named `save`
# are not checkpoint ops.
_GC007_LEAVES = frozenset(
    {
        "save",
        "restore",
        "wait_until_finished",
        "check_for_errors",
        "delete",
        "psum",
        "pmean",
        "pmax",
        "pmin",
        "all_gather",
        "all_reduce",
        "ppermute",
        "all_to_all",
        "sync_global_devices",
        "process_allgather",
        "broadcast_one_to_all",
    }
)


def _gc007_broad(handler: ast.ExceptHandler) -> tp.Optional[str]:
    """The broad class name a handler catches, or None if it is specific."""
    t = handler.type
    if t is None:
        return "<bare>"
    names = [e for e in (t.elts if isinstance(t, ast.Tuple) else [t])]
    for e in names:
        d = _dotted(e)
        if d in ("Exception", "BaseException"):
            return d
    return None


def _rule_gc007(mod: _Module) -> tp.Iterator[Finding]:
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Try):
            continue
        calls: tp.Set[str] = set()
        for stmt in node.body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Call):
                    name = _call_name(sub)
                    if name and "." in name and name.split(".")[-1] in _GC007_LEAVES:
                        calls.add(name)
        if not calls:
            continue
        for handler in node.handlers:
            broad = _gc007_broad(handler)
            if broad is None:
                continue
            swallows = not any(
                isinstance(sub, ast.Raise)
                for stmt in handler.body
                for sub in ast.walk(stmt)
            )
            if swallows:
                caught = "bare `except:`" if broad == "<bare>" else f"`except {broad}`"
                yield Finding(
                    "GC007",
                    mod.path,
                    handler.lineno,
                    handler.col_offset,
                    f"{caught} swallows failures of checkpoint/collective "
                    f"call(s) {sorted(calls)} — a dropped save/restore loses "
                    "state and a dropped collective deadlocks the mesh; "
                    "catch specific exceptions or re-raise (suppress with "
                    "justification if the swallow is deliberate)",
                )


# int8 dtype spellings GC008 recognizes as a quantizing cast target.
_INT8_DTYPES = frozenset(
    {"int8", "jnp.int8", "np.int8", "numpy.int8", "jax.numpy.int8"}
)
# Calls in the cast's receiver that count as rounding evidence. `clip` is
# deliberately NOT enough — clip(x, -127, 127).astype(int8) still truncates.
_ROUNDING_LEAVES = frozenset({"round", "rint", "around", "round_"})


def _rule_gc008(mod: _Module) -> tp.Iterator[Finding]:
    """`x.astype(jnp.int8)` / `x.astype("int8")` with no rounding call in
    the receiver expression. AST-only, so the source's float-ness cannot be
    proven — an int-to-int8 narrowing is a legitimate suppression (the
    justification documents why truncation is safe there)."""
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute) and f.attr == "astype"):
            continue
        target: tp.Optional[ast.AST] = node.args[0] if node.args else None
        if target is None:
            for kw in node.keywords:
                if kw.arg == "dtype":
                    target = kw.value
        if target is None:
            continue
        is_int8 = _dotted(target) in _INT8_DTYPES or (
            isinstance(target, ast.Constant) and target.value == "int8"
        )
        if not is_int8:
            continue
        rounded = any(
            isinstance(sub, ast.Call)
            and (_call_name(sub) or "").split(".")[-1] in _ROUNDING_LEAVES
            for sub in ast.walk(f.value)
        )
        if not rounded:
            yield Finding(
                "GC008",
                mod.path,
                node.lineno,
                node.col_offset,
                "`.astype(int8)` truncates toward zero — quantization must "
                "round-to-nearest first (jnp.round / ops/quant.py "
                "quantize_q8); suppress with justification if the source "
                "is already integral",
            )


# Wall-clock reads GC012 recognizes. `sleep` is absent on purpose (a delay,
# not a measurement) and so are the *_ns variants' non-time roots — only
# calls rooted at the `time` module count.
_GC012_CLOCK_LEAVES = frozenset(
    {
        "time",
        "perf_counter",
        "monotonic",
        "process_time",
        "time_ns",
        "perf_counter_ns",
        "monotonic_ns",
        "process_time_ns",
    }
)


def _gc012_in_scope(path: str) -> bool:
    """Path-scoped: only `sampling/` and `robustness/` trees — the hot
    paths where the injectable-clock discipline is load-bearing."""
    parts = re.split(r"[/\\]", path)
    return "sampling" in parts or "robustness" in parts


def _rule_gc012(mod: _Module) -> tp.Iterator[Finding]:
    """Bare clock CALLS in injectable-clock territory. A reference like
    `clock=time.perf_counter` (ctor default) is the plumbing itself and is
    a Name/Attribute node, not a Call — never flagged."""
    if not _gc012_in_scope(mod.path):
        return
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        if not name or "." not in name:
            continue
        parts = name.split(".")
        if parts[0] == "time" and parts[-1] in _GC012_CLOCK_LEAVES:
            yield Finding(
                "GC012",
                mod.path,
                node.lineno,
                node.col_offset,
                f"`{name}()` bypasses the injected clock in a serving/"
                "robustness hot path — read `self._clock()` (or the "
                "module's `clock` parameter) so tests can fake time and "
                "round decomposition stays on one clock "
                "(docs/OBSERVABILITY.md); suppress with justification "
                "for genuinely wall-anchored timestamps",
            )


_ALL_RULES = (
    _rule_gc001,
    _rule_gc002,
    _rule_gc003,
    _rule_gc004,
    _rule_gc005,
    _rule_gc006,
    _rule_gc007,
    _rule_gc008,
    _rule_gc012,
)


# ----------------------------------------------------------------------
# driver
# ----------------------------------------------------------------------


def lint_source(
    source: str,
    path: str = "<string>",
    rules: tp.Optional[tp.Iterable[str]] = None,
) -> tp.Tuple[tp.List[Finding], tp.List[Finding]]:
    """Lint one module's source. Returns (active, suppressed) findings."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        f = Finding("GC000", path, e.lineno or 0, e.offset or 0, f"syntax error: {e.msg}")
        return [f], []
    mod = _Module(path, source, tree)
    wanted = set(rules) if rules is not None else set(RULES)
    suppress_at: tp.Dict[int, tp.Set[str]] = {}
    for s in parse_suppressions(source):
        suppress_at.setdefault(s.line, set()).update(s.rules)
    active: tp.List[Finding] = []
    suppressed: tp.List[Finding] = []
    for rule_fn in _ALL_RULES:
        for f in rule_fn(mod):
            if f.rule not in wanted:
                continue
            if f.rule in suppress_at.get(f.line, ()):
                suppressed.append(f)
            else:
                active.append(f)
    active.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return active, suppressed


def iter_python_files(roots: tp.Sequence[str]) -> tp.Iterator[str]:
    for root in roots:
        if os.path.isfile(root):
            if root.endswith(".py"):
                yield root
        else:
            for dirpath, dirnames, filenames in os.walk(root):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        yield os.path.join(dirpath, fn)


def lint_paths(
    paths: tp.Sequence[str],
    rules: tp.Optional[tp.Iterable[str]] = None,
) -> tp.Tuple[tp.List[Finding], tp.List[Finding], int]:
    """Lint files/trees. Returns (active, suppressed, files_scanned)."""
    active: tp.List[Finding] = []
    suppressed: tp.List[Finding] = []
    n = 0
    for path in iter_python_files(paths):
        n += 1
        with open(path, "r", encoding="utf-8") as fh:
            src = fh.read()
        a, s = lint_source(src, path, rules)
        active.extend(a)
        suppressed.extend(s)
    return active, suppressed, n
