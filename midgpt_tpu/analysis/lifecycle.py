"""graftcheck pass 3: lifecycle + concurrency dataflow over the serving stack.

Deliberately JAX-free, like pass 1 (analysis/lint.py), whose Finding and
suppression machinery this pass shares. Where pass 1 flags single-site
footguns, pass 3 tracks *obligations* across paths:

  GC009  page-set / refcount lifecycle. Every acquisition site — pool
         `pool.alloc` / `allocator.alloc`, trie `prefix_cache.match` (takes
         refs), `prefix_cache.evict` / `prefix_cache.release` (both RETURN
         freed page lists that must reach `pool.free` / `allocator.free`)
         — must reach exactly
         one release funnel on every path, including explicit `raise`
         edges. Flags: discarded acquisition results, rebinding a variable
         that still holds pages, falling off a return/raise/function end
         with pages pending, releasing the same pages twice, `.refs`
         mutations outside the trie module, and a `.refs -=` with no
         adjacent underflow guard.
  GC010  async discipline around the serving driver loop
         (sampling/server.py): engine state is single-threaded by
         CONVENTION — only the driver loop (between `to_thread(step)`
         dispatches) may touch ServeEngine/trie/allocator state. Flags a
         direct `*.engine.*` method call or attribute store inside an
         `async def` body (must route through the command queue /
         `_call`), and an `await` interleaved between two mutations of
         the same `self.<attr>` in one block (a coroutine observing the
         half-updated state is the bug chaos_serve can only catch
         trace-by-trace).
  GC011  bounded static domains. Values flowing into a static jit
         argument (`static_argnums`) key the compile cache; an unbounded
         Python value there is an unbounded compile set (the recompile
         pins' bug class, made lexical). Every call-site expression at a
         static position must be PROVABLY drawn from a finite domain:
         literals, init-frozen `self` attributes, pow2 ladders
         (`.bit_length()`), normalizer/bucket/clamp calls, min/max against
         a bound, or parameters whose in-repo call sites all pass bounded
         values (interprocedural, depth-limited).

Scope model and limits (docs/ANALYSIS.md "Pass 3"): receiver names are
matched by hint (`pool` / `allocator` / `prefix_cache` / `trie` path components, or
locals aliased from one), so the trie module's own internals — which by
design mutate `.refs` and shuffle page lists — are exempt, as is any
`re.match`-style lookalike. Analysis is per-function for GC009/GC010 and
interprocedural-by-bare-name for GC011; like pass 1 it trades soundness
for zero false-positive noise on idiomatic code, and an unprovable-but-
intended domain takes a justified suppression.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import typing as tp

from midgpt_tpu.analysis.lint import (
    Finding,
    _FuncDef,
    _call_name,
    _dotted,
    _is_jax_jit,
    _partial_of,
    _unwrap_callable,
    iter_python_files,
    parse_suppressions,
)

LIFECYCLE_RULES: tp.Dict[str, str] = {
    "GC009": "page-set/refcount obligation leaked, discarded, or double-released",
    "GC010": "engine state touched outside the driver-loop serialization boundary",
    "GC011": "unbounded value feeds a static jit argument (compile-cache key)",
}

# ----------------------------------------------------------------------
# shared helpers
# ----------------------------------------------------------------------

_NESTED_SCOPES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef)


def _own_nodes(root: ast.AST) -> tp.Iterator[ast.AST]:
    """Walk `root` without descending into nested function/class scopes."""
    stack: tp.List[ast.AST] = list(ast.iter_child_nodes(root))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, _NESTED_SCOPES):
            stack.extend(ast.iter_child_nodes(node))


def _chain(node: ast.AST) -> tp.Tuple[str, ...]:
    """('a', 'b', 'c') for an a.b.c Name/Attribute chain, else ()."""
    dotted = _dotted(node)
    return tuple(dotted.split(".")) if dotted else ()


# `pool`: sampling/pages.py PagePool, whose `alloc(kind, n)` / `free(kind,
# pages)` are the allocators' by kind
_ALLOC_HINTS = ("allocator", "pool")
_TRIE_HINTS = ("prefix_cache", "trie")


def _hinted(func: ast.AST, hints: tp.Tuple[str, ...], aliases: tp.Set[str]) -> bool:
    """Does the receiver chain of a call target carry a structure hint?"""
    parts = _chain(func)
    if len(parts) < 2:
        return False
    recv = parts[:-1]
    return any(p in hints for p in recv) or recv[0] in aliases


# ----------------------------------------------------------------------
# GC009 — page-set / refcount lifecycle
# ----------------------------------------------------------------------

_PENDING, _RELEASED, _TRANSFERRED = "pending", "released", "transferred"

# call leaves that transfer ownership of a page-list argument into a
# container (slot.pages.extend(got), table.append(pages), ...)
_TRANSFER_LEAVES = {"extend", "append", "appendleft", "insert", "add", "push"}


@dataclasses.dataclass
class _Ob:
    """One outstanding page-set obligation bound to a local name."""

    line: int
    kind: str  # "alloc" | "match" | "evict" | "release"
    state: str = _PENDING


class _PageWalker:
    """Path-sensitive walk of one function body tracking page obligations."""

    def __init__(self, path: str, fn: _FuncDef, findings: tp.List[Finding]):
        self.path = path
        self.fn = fn
        self.findings = findings
        # locals aliased to a hinted structure: `pc = self.prefill.prefix_cache`
        self.alloc_aliases: tp.Set[str] = set()
        self.trie_aliases: tp.Set[str] = set()
        for node in _own_nodes(fn):
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
            ):
                parts = _chain(node.value)
                if any(p in _ALLOC_HINTS for p in parts):
                    self.alloc_aliases.add(node.targets[0].id)
                if any(p in _TRIE_HINTS for p in parts):
                    self.trie_aliases.add(node.targets[0].id)

    # -- call classification -------------------------------------------

    def _acquire_kind(self, call: ast.Call) -> tp.Optional[str]:
        parts = _chain(call.func)
        if not parts:
            return None
        leaf = parts[-1]
        if leaf == "alloc" and _hinted(call.func, _ALLOC_HINTS, self.alloc_aliases):
            return "alloc"
        if leaf in ("match", "evict", "release") and _hinted(
            call.func, _TRIE_HINTS, self.trie_aliases
        ):
            return leaf
        return None

    def _is_consume(self, call: ast.Call) -> bool:
        """A call that retires a page-set obligation passed as an argument."""
        parts = _chain(call.func)
        if not parts:
            return False
        leaf = parts[-1]
        if leaf == "free" and _hinted(call.func, _ALLOC_HINTS, self.alloc_aliases):
            return True
        # trie release(tokens, pages, n_shared): the pages arg is donated
        if leaf == "release" and _hinted(call.func, _TRIE_HINTS, self.trie_aliases):
            return True
        return False

    def _is_transfer_call(self, call: ast.Call) -> bool:
        parts = _chain(call.func)
        return bool(parts) and parts[-1] in _TRANSFER_LEAVES

    # -- findings -------------------------------------------------------

    def _emit(self, line: int, col: int, message: str) -> None:
        self.findings.append(Finding("GC009", self.path, line, col, message))

    # -- statement walk -------------------------------------------------

    def run(self) -> None:
        env: tp.Dict[str, _Ob] = {}
        terminated = self._walk_block(self.fn.body, env)
        if terminated is None:
            for name, ob in env.items():
                if ob.state == _PENDING:
                    self._emit(
                        ob.line,
                        0,
                        f"pages acquired into `{name}` (via .{ob.kind}) never "
                        "reach a release funnel on the fall-through path",
                    )

    def _walk_block(
        self, stmts: tp.Sequence[ast.stmt], env: tp.Dict[str, _Ob]
    ) -> tp.Optional[str]:
        for st in stmts:
            t = self._walk_stmt(st, env)
            if t is not None:
                return t
        return None

    def _walk_stmt(self, st: ast.stmt, env: tp.Dict[str, _Ob]) -> tp.Optional[str]:
        if isinstance(st, _NESTED_SCOPES):
            # a nested def/class capturing a pending name => ownership
            # escapes local reasoning; treat as transferred
            for node in ast.walk(st):
                if isinstance(node, ast.Name) and node.id in env:
                    if env[node.id].state == _PENDING:
                        env[node.id].state = _TRANSFERRED
            return None
        if isinstance(st, ast.If):
            return self._walk_if(st, env)
        if isinstance(st, (ast.For, ast.While, ast.AsyncFor)):
            return self._walk_loop(st, env)
        if isinstance(st, ast.Try):
            return self._walk_try(st, env)
        if isinstance(st, (ast.With, ast.AsyncWith)):
            for item in st.items:
                self._process_expr(item.context_expr, env, in_test=False)
            return self._walk_block(st.body, env)
        if isinstance(st, ast.Return):
            if st.value is not None:
                self._process_expr(st.value, env, in_test=False)
            self._leak_check(env, st.lineno, "at this return")
            return "return"
        if isinstance(st, ast.Raise):
            if st.exc is not None:
                self._process_expr(st.exc, env, in_test=False)
            if not self._inside_protected_try(st):
                self._leak_check(env, st.lineno, "on this exception edge")
            return "raise"
        if isinstance(st, (ast.Break, ast.Continue)):
            return "break"
        if isinstance(st, ast.Assign):
            return self._walk_assign(st, env)
        if isinstance(st, (ast.AugAssign, ast.AnnAssign)):
            if getattr(st, "value", None) is not None:
                self._process_expr(st.value, env, in_test=False, binds=True)
            return None
        if isinstance(st, ast.Expr):
            self._process_expr(st.value, env, in_test=False)
            return None
        if isinstance(st, ast.Assert):
            self._process_expr(st.test, env, in_test=True)
            return None
        # default: scan any embedded expressions conservatively
        for child in ast.iter_child_nodes(st):
            if isinstance(child, ast.expr):
                self._process_expr(child, env, in_test=False)
        return None

    def _walk_assign(self, st: ast.Assign, env: tp.Dict[str, _Ob]) -> None:
        value = st.value
        simple_name = (
            st.targets[0].id
            if len(st.targets) == 1 and isinstance(st.targets[0], ast.Name)
            else None
        )
        kind = self._acquire_kind(value) if isinstance(value, ast.Call) else None
        if kind is not None and simple_name is not None:
            # process the acquire call's ARGUMENTS (they may consume other
            # tracked names), but not the call itself
            for arg in list(value.args) + [kw.value for kw in value.keywords]:
                self._process_expr(arg, env, in_test=False)
            old = env.get(simple_name)
            if old is not None and old.state == _PENDING:
                self._emit(
                    st.lineno,
                    st.col_offset,
                    f"`{simple_name}` rebound while still holding pages "
                    f"acquired at line {old.line} — the old pages leak",
                )
            env[simple_name] = _Ob(st.lineno, kind)
            return None
        self._process_expr(value, env, in_test=False, binds=True)
        if simple_name is not None:
            old = env.get(simple_name)
            if old is not None and old.state == _PENDING:
                # RHS uses were processed above; a rebind that did not
                # route the old pages anywhere loses them
                if not any(
                    isinstance(n, ast.Name) and n.id == simple_name
                    for n in ast.walk(value)
                ):
                    self._emit(
                        st.lineno,
                        st.col_offset,
                        f"`{simple_name}` rebound while still holding pages "
                        f"acquired at line {old.line} — the old pages leak",
                    )
            env.pop(simple_name, None)
        return None

    def _walk_if(self, st: ast.If, env: tp.Dict[str, _Ob]) -> tp.Optional[str]:
        self._process_expr(st.test, env, in_test=True)
        refine_body, refine_else = self._refiners(st.test)
        env_body = {k: dataclasses.replace(v) for k, v in env.items()}
        env_else = {k: dataclasses.replace(v) for k, v in env.items()}
        refine_body(env_body)
        refine_else(env_else)
        t_body = self._walk_block(st.body, env_body)
        t_else = self._walk_block(st.orelse, env_else) if st.orelse else None
        branches = []
        if t_body is None:
            branches.append(env_body)
        if t_else is None:
            branches.append(env_else)
        if not branches:
            env.clear()
            return "return"  # both arms terminated: this block is done
        self._merge_into(env, branches)
        return None

    def _walk_loop(self, st: ast.stmt, env: tp.Dict[str, _Ob]) -> tp.Optional[str]:
        if isinstance(st, (ast.For, ast.AsyncFor)):
            self._process_expr(st.iter, env, in_test=False)
        else:
            self._process_expr(st.test, env, in_test=True)
        env_body = {k: dataclasses.replace(v) for k, v in env.items()}
        self._walk_block(st.body, env_body)
        if st.orelse:
            self._walk_block(st.orelse, env_body)
        self._merge_into(env, [env, env_body])
        return None

    def _walk_try(self, st: ast.Try, env: tp.Dict[str, _Ob]) -> tp.Optional[str]:
        entry = {k: dataclasses.replace(v) for k, v in env.items()}
        t_body = self._walk_block(st.body, env)
        exits: tp.List[tp.Dict[str, _Ob]] = []
        if t_body is None:
            exits.append(env)
        for handler in st.handlers:
            # the exception may land anywhere in the body: the handler sees
            # anything between the entry state and the body-exit state —
            # union with pending winning is the pessimistic approximation
            env_h = {k: dataclasses.replace(v) for k, v in entry.items()}
            self._merge_into(env_h, [env_h, env])
            t_h = self._walk_block(handler.body, env_h)
            if t_h is None:
                exits.append(env_h)
        merged: tp.Dict[str, _Ob] = {}
        if exits:
            self._merge_into(merged, exits)
        t_final = None
        if st.finalbody:
            t_final = self._walk_block(st.finalbody, merged)
        env.clear()
        env.update(merged)
        if not exits:
            return "return"
        return t_final

    def _inside_protected_try(self, node: ast.AST) -> bool:
        """Is `node` lexically inside a try-with-handlers of this function?
        The handler walk covers those paths; flagging the raise too would
        double-report guarded cleanup idioms."""
        for anc in ast.walk(self.fn):
            if isinstance(anc, ast.Try) and anc.handlers:
                for sub in ast.walk(anc):
                    if sub is node:
                        return True
        return False

    def _leak_check(self, env: tp.Dict[str, _Ob], line: int, where: str) -> None:
        for name, ob in env.items():
            if ob.state == _PENDING:
                self._emit(
                    line,
                    0,
                    f"pages acquired into `{name}` at line {ob.line} "
                    f"(via .{ob.kind}) are still unreleased {where}",
                )
                ob.state = _TRANSFERRED  # one report per obligation per path

    def _merge_into(
        self, dst: tp.Dict[str, _Ob], branches: tp.List[tp.Dict[str, _Ob]]
    ) -> None:
        names: tp.Set[str] = set()
        for b in branches:
            names.update(b)
        out: tp.Dict[str, _Ob] = {}
        for name in names:
            obs = [b[name] for b in branches if name in b]
            pending = [o for o in obs if o.state == _PENDING]
            out[name] = dataclasses.replace(pending[0] if pending else obs[0])
        dst.clear()
        dst.update(out)

    # -- expression-level processing -----------------------------------

    def _process_expr(
        self,
        expr: ast.expr,
        env: tp.Dict[str, _Ob],
        in_test: bool,
        binds: bool = False,
    ) -> None:
        """Handle acquires and tracked-name uses inside one expression.

        `in_test` — condition position: uses refine, never transfer.
        `binds` — the expression's value is stored/returned: plain uses
        transfer ownership instead of being neutral reads.
        """
        consume_args: tp.Set[int] = set()
        transfer_args: tp.Set[int] = set()
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            if self._is_consume(node):
                for sub in node.args:
                    for n2 in ast.walk(sub):
                        consume_args.add(id(n2))
            elif self._is_transfer_call(node):
                for sub in node.args:
                    for n2 in ast.walk(sub):
                        transfer_args.add(id(n2))
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            kind = self._acquire_kind(node)
            if kind is None:
                continue
            if id(node) in consume_args or id(node) in transfer_args:
                continue  # free(release(...)) — acquired and retired inline
            if binds:
                continue  # bound into a larger value: ownership escapes
            self._emit(
                node.lineno,
                node.col_offset,
                f"result of .{kind}() is discarded — the returned pages/refs "
                "can never reach a release funnel",
            )
        for node in ast.walk(expr):
            if not isinstance(node, ast.Name) or node.id not in env:
                continue
            ob = env[node.id]
            if id(node) in consume_args:
                if ob.state == _RELEASED:
                    self._emit(
                        node.lineno,
                        node.col_offset,
                        f"`{node.id}` released again — pages from line "
                        f"{ob.line} already reached a release funnel",
                    )
                ob.state = _RELEASED
            elif id(node) in transfer_args:
                if ob.state == _PENDING:
                    ob.state = _TRANSFERRED
            elif in_test:
                pass  # condition reads refine (see _refiners), never move
            elif ob.state == _PENDING:
                ob.state = _TRANSFERRED

    def _refiners(
        self, test: ast.expr
    ) -> tp.Tuple[tp.Callable[[tp.Dict[str, _Ob]], None], tp.Callable[[tp.Dict[str, _Ob]], None]]:
        """Falsy-acquisition refinement: alloc may return None, match/evict
        may return an empty set — the falsy branch carries no obligation."""

        def clear(name: str) -> tp.Callable[[tp.Dict[str, _Ob]], None]:
            return lambda env: env.pop(name, None)

        def keep(env: tp.Dict[str, _Ob]) -> None:
            return None

        root = self._test_root(test)
        if isinstance(test, ast.Compare) and len(test.ops) == 1:
            left_root = self._test_root(test.left)
            is_none = (
                isinstance(test.comparators[0], ast.Constant)
                and test.comparators[0].value is None
            )
            if left_root and is_none:
                if isinstance(test.ops[0], ast.Is):
                    return clear(left_root), keep
                if isinstance(test.ops[0], ast.IsNot):
                    return keep, clear(left_root)
            return keep, keep
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            inner = self._test_root(test.operand)
            if inner:
                return clear(inner), keep
            return keep, keep
        if root:
            return keep, clear(root)
        return keep, keep

    @staticmethod
    def _test_root(node: ast.expr) -> tp.Optional[str]:
        parts = _chain(node)
        return parts[0] if parts else None


def _rule_gc009(path: str, tree: ast.Module) -> tp.Iterator[Finding]:
    for fn in ast.walk(tree):
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            findings: tp.List[Finding] = []
            _PageWalker(path, fn, findings).run()
            yield from findings
    yield from _refs_protocol(path, tree)


def _refs_protocol(path: str, tree: ast.Module) -> tp.Iterator[Finding]:
    """The trie refcount protocol: `.refs` is mutated ONLY inside the trie
    module, and every decrement carries an adjacent underflow guard."""
    owning = os.path.basename(path) == "prefix_cache.py"
    for node in ast.walk(tree):
        blocks: tp.List[tp.List[ast.stmt]] = []
        for field in ("body", "orelse", "finalbody"):
            b = getattr(node, field, None)
            if isinstance(b, list) and b and isinstance(b[0], ast.stmt):
                blocks.append(b)
        for block in blocks:
            for i, st in enumerate(block):
                tgt = None
                if isinstance(st, (ast.Assign, ast.AugAssign)):
                    targets = st.targets if isinstance(st, ast.Assign) else [st.target]
                    for t in targets:
                        if isinstance(t, ast.Attribute) and t.attr == "refs":
                            tgt = t
                if tgt is None:
                    continue
                if not owning:
                    yield Finding(
                        "GC009",
                        path,
                        st.lineno,
                        st.col_offset,
                        "`.refs` mutated outside the trie module — refcount "
                        "conservation is prefix_cache.py-internal protocol",
                    )
                    continue
                if isinstance(st, ast.AugAssign) and isinstance(st.op, ast.Sub):
                    nxt = block[i + 1] if i + 1 < len(block) else None
                    guarded = isinstance(nxt, ast.Assert) and any(
                        isinstance(n, ast.Attribute) and n.attr == "refs"
                        for n in ast.walk(nxt.test)
                    )
                    if not guarded:
                        yield Finding(
                            "GC009",
                            path,
                            st.lineno,
                            st.col_offset,
                            "`.refs -=` without an adjacent underflow guard "
                            "(assert ... refs >= 0) — a silent negative "
                            "refcount unbalances the trie",
                        )


# ----------------------------------------------------------------------
# GC010 — async discipline around the driver loop
# ----------------------------------------------------------------------

_MUT_LEAVES = {
    "append",
    "appendleft",
    "extend",
    "insert",
    "add",
    "pop",
    "popleft",
    "remove",
    "discard",
    "clear",
    "update",
    "setdefault",
}


def _self_mutations(st: ast.stmt) -> tp.Set[str]:
    """First-level `self` attributes this statement mutates."""
    out: tp.Set[str] = set()
    for node in _own_nodes_stmt(st):
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (
                node.targets if isinstance(node, ast.Assign) else [node.target]
            )
            for t in targets:
                attr = _self_attr_root(t)
                if attr:
                    out.add(attr)
        elif isinstance(node, ast.Call):
            parts = _chain(node.func)
            if len(parts) >= 3 and parts[0] == "self" and parts[-1] in _MUT_LEAVES:
                out.add(parts[1])
    return out


def _self_attr_root(target: ast.expr) -> tp.Optional[str]:
    """'x' for self.x..., self.x[...] = ... store targets."""
    node = target
    if isinstance(node, ast.Subscript):
        node = node.value
    parts = _chain(node)
    if len(parts) >= 2 and parts[0] == "self":
        return parts[1]
    return None


def _own_nodes_stmt(st: ast.stmt) -> tp.Iterator[ast.AST]:
    yield st
    yield from _own_nodes(st)


def _has_await(st: ast.stmt) -> bool:
    return any(isinstance(n, ast.Await) for n in _own_nodes_stmt(st))


def _rule_gc010(path: str, tree: ast.Module) -> tp.Iterator[Finding]:
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.AsyncFunctionDef):
            continue
        # A: direct engine access from the event-loop context. The engine
        # is stepped on a worker thread; only queued commands (nested defs
        # and lambdas — excluded from _own_nodes — drained by the driver)
        # may call into it.
        for node in _own_nodes(fn):
            if isinstance(node, ast.Call):
                parts = _chain(node.func)
                if len(parts) >= 3 and "engine" in parts[1:-1] or (
                    len(parts) >= 2 and parts[0] == "engine"
                ):
                    yield Finding(
                        "GC010",
                        path,
                        node.lineno,
                        node.col_offset,
                        f"direct engine call `{'.'.join(parts)}` inside "
                        f"`async def {fn.name}` — engine state is driver-"
                        "loop-only; route through the command queue "
                        "(_call / to_thread boundary)",
                    )
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets if isinstance(node, ast.Assign) else [node.target]
                )
                for t in targets:
                    parts = _chain(t if not isinstance(t, ast.Subscript) else t.value)
                    if "engine" in parts[:-1]:
                        yield Finding(
                            "GC010",
                            path,
                            node.lineno,
                            node.col_offset,
                            f"store to `{'.'.join(parts)}` inside "
                            f"`async def {fn.name}` — engine state is "
                            "driver-loop-only; route through the command "
                            "queue",
                        )
        # B: await interleaved inside a mutation-in-progress region — two
        # mutations of the same self attribute in one block with an await
        # between them hand the half-updated state to other coroutines.
        yield from _await_mid_mutation(path, fn)


def _await_mid_mutation(path: str, fn: ast.AsyncFunctionDef) -> tp.Iterator[Finding]:
    blocks: tp.List[tp.List[ast.stmt]] = []
    stack: tp.List[ast.AST] = [fn]
    while stack:
        node = stack.pop()
        for field in ("body", "orelse", "finalbody"):
            b = getattr(node, field, None)
            if isinstance(b, list) and b and isinstance(b[0], ast.stmt):
                blocks.append(b)
        for child in ast.iter_child_nodes(node):
            if not isinstance(child, _NESTED_SCOPES):
                stack.append(child)
        if isinstance(node, ast.Try):
            stack.extend(h for h in node.handlers)
    for block in blocks:
        muts = [(_self_mutations(st), _has_await(st), st) for st in block]
        attrs: tp.Set[str] = set()
        for m, _, _ in muts:
            attrs.update(m)
        for attr in sorted(attrs):
            idx = [i for i, (m, _, _) in enumerate(muts) if attr in m]
            if len(idx) < 2:
                continue
            for j in range(idx[0] + 1, idx[-1]):
                if j in idx:
                    continue
                if muts[j][1]:
                    st = muts[j][2]
                    yield Finding(
                        "GC010",
                        path,
                        st.lineno,
                        st.col_offset,
                        f"`await` between two mutations of `self.{attr}` "
                        "in one block — another coroutine can observe the "
                        "mutation-in-progress state",
                    )


# ----------------------------------------------------------------------
# GC011 — bounded static jit-argument domains
# ----------------------------------------------------------------------


@dataclasses.dataclass
class _JitInfo:
    name: str
    path: str
    fn: _FuncDef
    statics: tp.Tuple[int, ...]


class _ModuleInfo:
    def __init__(self, path: str, tree: ast.Module):
        self.path = path
        self.tree = tree
        self.parents: tp.Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                self.parents[child] = parent
        self.defs_by_name: tp.Dict[str, tp.List[_FuncDef]] = {}
        for n in ast.walk(tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs_by_name.setdefault(n.name, []).append(n)
        # module-level constants (Name = <expr> at module scope)
        self.module_assigns: tp.Dict[str, tp.List[ast.expr]] = {}
        for st in tree.body:
            if isinstance(st, ast.Assign) and len(st.targets) == 1:
                t = st.targets[0]
                if isinstance(t, ast.Name):
                    self.module_assigns.setdefault(t.id, []).append(st.value)

    def enclosing_function(self, node: ast.AST) -> tp.Optional[_FuncDef]:
        cur: tp.Optional[ast.AST] = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self.parents.get(cur)
        return None

    def enclosing_class(self, node: ast.AST) -> tp.Optional[ast.ClassDef]:
        cur: tp.Optional[ast.AST] = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, ast.ClassDef):
                return cur
            cur = self.parents.get(cur)
        return None


class _Index:
    """Cross-module (bare-name) index for the GC011 boundedness prover."""

    def __init__(self, modules: tp.List[_ModuleInfo]):
        self.modules = modules
        self.jits: tp.Dict[str, _JitInfo] = {}
        self.callsites: tp.Dict[
            str, tp.List[tp.Tuple[_ModuleInfo, ast.Call]]
        ] = {}
        for mod in modules:
            self._index_jits(mod)
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Call):
                    name = _call_name(node)
                    if name:
                        leaf = name.split(".")[-1]
                        self.callsites.setdefault(leaf, []).append((mod, node))

    @staticmethod
    def _statics_from_call(call: ast.Call) -> tp.Tuple[int, ...]:
        for kw in call.keywords:
            if kw.arg in ("static_argnums", "static_argnames"):
                v = kw.value
                if isinstance(v, ast.Constant) and isinstance(v.value, int):
                    return (v.value,)
                if isinstance(v, (ast.Tuple, ast.List)):
                    return tuple(
                        e.value
                        for e in v.elts
                        if isinstance(e, ast.Constant) and isinstance(e.value, int)
                    )
        return ()

    def _index_jits(self, mod: _ModuleInfo) -> None:
        for defs in mod.defs_by_name.values():
            for d in defs:
                for deco in d.decorator_list:
                    if not isinstance(deco, ast.Call):
                        continue
                    inner = _partial_of(deco)
                    is_jit = _is_jax_jit(deco.func) or (
                        inner is not None and _is_jax_jit(inner)
                    )
                    statics = self._statics_from_call(deco)
                    if is_jit and statics:
                        self.jits[d.name] = _JitInfo(d.name, mod.path, d, statics)
        # name = jax.jit(fn, static_argnums=...) rebinding
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Assign) or not isinstance(
                node.value, ast.Call
            ):
                continue
            call = node.value
            if not _is_jax_jit(call.func) or not call.args:
                continue
            statics = self._statics_from_call(call)
            target = _unwrap_callable(call.args[0])
            if statics and target:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        leaf = target.split(".")[-1]
                        for d in mod.defs_by_name.get(leaf, []):
                            self.jits[t.id] = _JitInfo(
                                t.id, mod.path, d, statics
                            )


_BOUNDED_CALL_MARKERS = ("bucket", "clamp")
_MAX_DEPTH = 6


class _BoundProver:
    """Proves a call-site expression draws from a finite domain."""

    def __init__(self, index: _Index):
        self.index = index

    def bounded(
        self,
        expr: ast.expr,
        mod: _ModuleInfo,
        fn: tp.Optional[_FuncDef],
        depth: int = 0,
        seen: tp.Optional[tp.Set[tp.Tuple]] = None,
    ) -> bool:
        seen = seen if seen is not None else set()
        if depth > _MAX_DEPTH:
            return True  # deep chains: give up optimistically (lint, not proof)
        if isinstance(expr, ast.Constant):
            return True
        if isinstance(expr, (ast.Tuple, ast.List)):
            return all(self.bounded(e, mod, fn, depth + 1, seen) for e in expr.elts)
        if isinstance(expr, ast.Compare):
            return True  # bool domain
        if isinstance(expr, ast.BoolOp):
            return all(
                self.bounded(v, mod, fn, depth + 1, seen) for v in expr.values
            )
        if isinstance(expr, ast.UnaryOp):
            return self.bounded(expr.operand, mod, fn, depth + 1, seen)
        if isinstance(expr, ast.BinOp):
            return self.bounded(
                expr.left, mod, fn, depth + 1, seen
            ) and self.bounded(expr.right, mod, fn, depth + 1, seen)
        if isinstance(expr, ast.IfExp):
            return self.bounded(
                expr.body, mod, fn, depth + 1, seen
            ) and self.bounded(expr.orelse, mod, fn, depth + 1, seen)
        if isinstance(expr, ast.Call):
            return self._bounded_call(expr, mod, fn, depth, seen)
        if isinstance(expr, ast.Attribute):
            return self._bounded_attr(expr, mod, fn, depth, seen)
        if isinstance(expr, ast.Name):
            return self._bounded_name(expr.id, mod, fn, depth, seen)
        return False

    def _bounded_call(
        self,
        call: ast.Call,
        mod: _ModuleInfo,
        fn: tp.Optional[_FuncDef],
        depth: int,
        seen: tp.Set[tp.Tuple],
    ) -> bool:
        name = _call_name(call)
        leaf = name.split(".")[-1] if name else ""
        if leaf == "bit_length":
            return True  # 1 << (x.bit_length() - 1): the pow2 ladder idiom
        if leaf.startswith("normalize") or any(
            m in leaf for m in _BOUNDED_CALL_MARKERS
        ):
            return True  # by convention: normalizers/buckets clamp to a menu
        if leaf in ("min", "max"):
            return any(
                self.bounded(a, mod, fn, depth + 1, seen) for a in call.args
            )
        # same-module def: bounded iff every return expression is bounded
        key = ("ret", mod.path, leaf)
        if key in seen:
            return True
        candidates = mod.defs_by_name.get(leaf, [])
        if candidates:
            seen.add(key)
            for d in candidates:
                for node in _own_nodes(d):
                    if isinstance(node, ast.Return) and node.value is not None:
                        if not self.bounded(node.value, mod, d, depth + 1, seen):
                            return False
            return True
        return False

    def _bounded_attr(
        self,
        expr: ast.Attribute,
        mod: _ModuleInfo,
        fn: tp.Optional[_FuncDef],
        depth: int,
        seen: tp.Set[tp.Tuple],
    ) -> bool:
        parts = _chain(expr)
        if not parts:
            return False
        if parts[0] == "self" and len(parts) >= 2 and fn is not None:
            return self._init_frozen(parts[1], mod, fn)
        # non-self root: an attribute of a bounded-identity object is drawn
        # from a finite per-object set
        return self._bounded_name(parts[0], mod, fn, depth + 1, seen)

    def _init_frozen(self, attr: str, mod: _ModuleInfo, fn: _FuncDef) -> bool:
        """self.<attr> is bounded when every store in the class happens in
        __init__ — the value is fixed per live instance."""
        cls = mod.enclosing_class(fn)
        if cls is None:
            return False
        for node in ast.walk(cls):
            if not isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                continue
            targets = (
                node.targets if isinstance(node, ast.Assign) else [node.target]
            )
            for t in targets:
                t2 = t.value if isinstance(t, ast.Subscript) else t
                p = _chain(t2)
                if len(p) >= 2 and p[0] == "self" and p[1] == attr:
                    owner = mod.enclosing_function(node)
                    if owner is None or owner.name != "__init__":
                        return False
        return True

    def _bounded_name(
        self,
        name: str,
        mod: _ModuleInfo,
        fn: tp.Optional[_FuncDef],
        depth: int,
        seen: tp.Set[tp.Tuple],
    ) -> bool:
        # resolve through the lexical scope chain: the function itself,
        # then enclosing functions (closure variables), then module scope
        scope = fn
        while scope is not None:
            key = ("name", mod.path, scope.name, name)
            if key in seen:
                return True  # self-referential clamp chains: bounded iff base
            assigns: tp.List[ast.expr] = []
            is_loop_target = False
            loop_iters: tp.List[ast.expr] = []
            for node in _own_nodes(scope):
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        if isinstance(t, ast.Name) and t.id == name:
                            assigns.append(node.value)
                        elif isinstance(t, (ast.Tuple, ast.List)):
                            # element-wise unpack: a, b = x, y
                            for j, e in enumerate(t.elts):
                                if not (isinstance(e, ast.Name) and e.id == name):
                                    continue
                                v = node.value
                                if isinstance(v, (ast.Tuple, ast.List)) and len(
                                    v.elts
                                ) == len(t.elts):
                                    assigns.append(v.elts[j])
                                else:
                                    assigns.append(v)  # opaque unpack source
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    if (
                        isinstance(node.target, ast.Name)
                        and node.target.id == name
                        and getattr(node, "value", None) is not None
                    ):
                        assigns.append(node.value)
                elif isinstance(node, (ast.For, ast.AsyncFor)):
                    for t in ast.walk(node.target):
                        if isinstance(t, ast.Name) and t.id == name:
                            is_loop_target = True
                            loop_iters.append(node.iter)
            if assigns or is_loop_target:
                seen.add(key)
                ok = all(
                    self.bounded(a, mod, scope, depth + 1, seen)
                    for a in assigns
                )
                ok = ok and all(
                    isinstance(it, (ast.Tuple, ast.List))
                    and all(isinstance(e, ast.Constant) for e in it.elts)
                    for it in loop_iters
                )
                return ok
            params = [a.arg for a in scope.args.args + scope.args.kwonlyargs]
            if name in params:
                return self._bounded_param(name, mod, scope, depth, seen)
            scope = mod.enclosing_function(scope)
        if name in mod.module_assigns:
            key = ("mod", mod.path, name)
            if key in seen:
                return True
            seen.add(key)
            return all(
                self.bounded(a, mod, None, depth + 1, seen)
                for a in mod.module_assigns[name]
            )
        return False

    def _bounded_param(
        self,
        name: str,
        mod: _ModuleInfo,
        fn: _FuncDef,
        depth: int,
        seen: tp.Set[tp.Tuple],
    ) -> bool:
        """A parameter is bounded when EVERY in-repo call site passes a
        bounded value (interprocedural, by bare callee name)."""
        key = ("param", mod.path, fn.name, name)
        if key in seen:
            return True
        seen.add(key)
        pos_params = [a.arg for a in fn.args.args]
        offset = 1 if pos_params and pos_params[0] in ("self", "cls") else 0
        try:
            pidx = pos_params.index(name)
        except ValueError:
            pidx = None
        defaults = fn.args.defaults
        default_expr: tp.Optional[ast.expr] = None
        if pidx is not None and defaults:
            d0 = len(pos_params) - len(defaults)
            if pidx >= d0:
                default_expr = defaults[pidx - d0]
        for kwp, kwd in zip(fn.args.kwonlyargs, fn.args.kw_defaults):
            if kwp.arg == name and kwd is not None:
                default_expr = kwd
        sites = self.index.callsites.get(fn.name, [])
        if not sites:
            return False  # callers unknown: the domain cannot be proven
        for smod, call in sites:
            arg_expr: tp.Optional[ast.expr] = None
            if pidx is not None:
                # instance-method call sites (obj.meth(...)) bind `self`
                # implicitly, shifting positional args left by one
                ai = pidx - (offset if isinstance(call.func, ast.Attribute) else 0)
                if 0 <= ai < len(call.args):
                    arg_expr = call.args[ai]
            if arg_expr is None:
                for kw in call.keywords:
                    if kw.arg == name:
                        arg_expr = kw.value
            if arg_expr is None:
                if default_expr is None:
                    continue  # not passed, no default: not this overload
                arg_expr = default_expr
                if isinstance(arg_expr, ast.Constant):
                    continue
            caller_fn = smod.enclosing_function(call)
            if not self.bounded(arg_expr, smod, caller_fn, depth + 1, seen):
                return False
        return True


def _rule_gc011(
    mod: _ModuleInfo, index: _Index
) -> tp.Iterator[Finding]:
    prover = _BoundProver(index)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) or not isinstance(node.func, ast.Name):
            continue
        info = index.jits.get(node.func.id)
        if info is None or mod.enclosing_function(node) is info.fn:
            continue
        params = [a.arg for a in info.fn.args.args]
        caller = mod.enclosing_function(node)
        for i in info.statics:
            arg_expr: tp.Optional[ast.expr] = None
            if i < len(node.args):
                arg_expr = node.args[i]
            elif i < len(params):
                for kw in node.keywords:
                    if kw.arg == params[i]:
                        arg_expr = kw.value
            if arg_expr is None:
                continue  # defaulted: the def's literal default is bounded
            if prover.bounded(arg_expr, mod, caller):
                continue
            pname = params[i] if i < len(params) else str(i)
            yield Finding(
                "GC011",
                mod.path,
                arg_expr.lineno,
                arg_expr.col_offset,
                f"static arg {i} (`{pname}`) of `{info.name}` takes a value "
                "not provably drawn from a finite domain — every distinct "
                "value compiles a new program; clamp through a normalizer/"
                "bucket or a literal menu",
            )


# ----------------------------------------------------------------------
# driver — mirrors lint_source / lint_paths
# ----------------------------------------------------------------------


def lifecycle_source(
    source: str,
    path: str = "<string>",
    rules: tp.Optional[tp.Iterable[str]] = None,
    index: tp.Optional[_Index] = None,
) -> tp.Tuple[tp.List[Finding], tp.List[Finding]]:
    """Run pass 3 on one module's source. Returns (active, suppressed).

    Without `index`, a single-module index is built (fixtures, ad-hoc
    runs); lifecycle_paths supplies the cross-module one. Syntax errors
    yield nothing — pass 1 already reports GC000 for the same file."""
    try:
        tree = ast.parse(source)
    except SyntaxError:
        return [], []
    wanted = set(rules) if rules is not None else set(LIFECYCLE_RULES)
    mod = _ModuleInfo(path, tree)
    if index is None:
        index = _Index([mod])
    findings: tp.List[Finding] = []
    if "GC009" in wanted:
        findings.extend(_rule_gc009(path, tree))
    if "GC010" in wanted:
        findings.extend(_rule_gc010(path, tree))
    if "GC011" in wanted:
        findings.extend(_rule_gc011(mod, index))
    suppress_at: tp.Dict[int, tp.Set[str]] = {}
    for s in parse_suppressions(source):
        suppress_at.setdefault(s.line, set()).update(s.rules)
    active: tp.List[Finding] = []
    suppressed: tp.List[Finding] = []
    for f in findings:
        if f.rule not in wanted:
            continue
        if f.rule in suppress_at.get(f.line, ()):
            suppressed.append(f)
        else:
            active.append(f)
    active.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return active, suppressed


def lifecycle_paths(
    paths: tp.Sequence[str],
    rules: tp.Optional[tp.Iterable[str]] = None,
) -> tp.Tuple[tp.List[Finding], tp.List[Finding], int]:
    """Run pass 3 over files/trees with a shared cross-module index."""
    sources: tp.List[tp.Tuple[str, str]] = []
    modules: tp.List[_ModuleInfo] = []
    for path in iter_python_files(paths):
        with open(path, "r", encoding="utf-8") as fh:
            src = fh.read()
        sources.append((path, src))
        try:
            modules.append(_ModuleInfo(path, ast.parse(src)))
        except SyntaxError:
            pass
    index = _Index(modules)
    active: tp.List[Finding] = []
    suppressed: tp.List[Finding] = []
    for path, src in sources:
        a, s = lifecycle_source(src, path, rules, index)
        active.extend(a)
        suppressed.extend(s)
    return active, suppressed, len(sources)
