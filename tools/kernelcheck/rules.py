"""kernelcheck rules R1-R8 (see DESIGN.md §12 for the catalog).

Each ``check_rN(index, ...)`` returns a list of Findings. Rules are
conservative by construction: anything unresolvable is treated as unknown
(consumed / host-side / safe), so a clean tree stays clean.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Sequence, Set, Tuple

from tools.kernelcheck.analyzer import (BLOCK_SPEC, PALLAS_CALL,
                                        SHAPE_DTYPE_STRUCT, WIDE_DTYPES,
                                        Finding, ModuleInfo, RepoIndex)

# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

#: plan container classes are dataclasses named *Plan/*Round/*Bucket
_PLAN_SUFFIXES = ("Plan", "Round", "Bucket")


def _is_dataclass(node: ast.ClassDef) -> bool:
    for dec in node.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        chain = ast.unparse(target)
        if "dataclass" in chain:
            return True
    return False


def _last_segment(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _dtype_token(index: RepoIndex, mi: ModuleInfo, node: ast.AST
                 ) -> Optional[str]:
    """'int64' for jnp.int64 / np.float64-style dtype expressions."""
    dotted = index.dotted(mi, node)
    if dotted is None:
        return None
    head, _, last = dotted.rpartition(".")
    if "numpy" in head or head.startswith("jax"):
        return last
    return None


def _raise_only(fn: ast.FunctionDef) -> bool:
    body = list(fn.body)
    if body and isinstance(body[0], ast.Expr) and isinstance(
            body[0].value, ast.Constant):
        body = body[1:]
    return len(body) == 1 and isinstance(body[0], ast.Raise)


# ---------------------------------------------------------------------------
# R1 — plan/kernel dtype agreement + dead plan fields
# ---------------------------------------------------------------------------


def _field_classes(index: RepoIndex, suffixes: Tuple[str, ...]
                   ) -> Dict[str, Dict[str, ast.AnnAssign]]:
    """class name -> {field name -> AnnAssign} for dataclasses whose name
    ends with one of ``suffixes``."""
    plans: Dict[str, Dict[str, ast.AnnAssign]] = {}
    for mi in index.modules.values():
        for cname, cnode in mi.classes.items():
            if not cname.endswith(suffixes) or not _is_dataclass(cnode):
                continue
            fields = {}
            for item in cnode.body:
                if isinstance(item, ast.AnnAssign) and isinstance(
                        item.target, ast.Name):
                    fields[item.target.id] = item
            if fields:
                plans[cname] = fields
    return plans


def _plan_classes(index: RepoIndex) -> Dict[str, Dict[str, ast.AnnAssign]]:
    """class name -> {field name -> AnnAssign} for plan dataclasses."""
    return _field_classes(index, _PLAN_SUFFIXES)


def _ann_type(ann: ast.AST, plans) -> Optional[Tuple[str, str]]:
    """Map a field/param annotation to ('inst'|'tuple', plan class name)."""
    if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
        try:
            ann = ast.parse(ann.value, mode="eval").body
        except SyntaxError:
            return None
    if isinstance(ann, ast.Name):
        return ("inst", ann.id) if ann.id in plans else None
    if isinstance(ann, ast.Attribute):
        return ("inst", ann.attr) if ann.attr in plans else None
    if isinstance(ann, ast.Subscript):
        base = _last_segment(ann.value)
        inner = ann.slice
        if base in ("Tuple", "tuple") and isinstance(inner, ast.Tuple) \
                and inner.elts:
            elem = _ann_type(inner.elts[0], plans)
            if elem is not None and elem[0] == "inst":
                return ("tuple", elem[1])
        if base == "Optional":
            return _ann_type(inner, plans)
    return None


class _Typing:
    """Per-function receiver typing: parameters annotated with plan classes,
    propagated through assignments, for-loops, comprehensions, tuple-field
    element access and subscripts."""

    def __init__(self, plans, field_types, fn: ast.FunctionDef):
        self.plans = plans
        self.field_types = field_types  # (cls, field) -> ('inst'|'tuple', cls)
        self.env: Dict[str, Tuple[str, str]] = {}
        args = fn.args
        for a in args.posonlyargs + args.args + args.kwonlyargs:
            if a.annotation is not None:
                t = _ann_type(a.annotation, plans)
                if t is not None:
                    self.env[a.arg] = t
        for _ in range(2):  # two passes propagate chained assignments
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name):
                    t = self.type_of(node.value)
                    if t is not None:
                        self.env[node.targets[0].id] = t
                elif isinstance(node, (ast.For, ast.comprehension)):
                    tgt = node.target
                    it = self.type_of(node.iter)
                    if isinstance(tgt, ast.Name) and it is not None \
                            and it[0] == "tuple":
                        self.env[tgt.id] = ("inst", it[1])

    def type_of(self, node: ast.AST) -> Optional[Tuple[str, str]]:
        if isinstance(node, ast.Name):
            return self.env.get(node.id)
        if isinstance(node, ast.Attribute):
            base = self.type_of(node.value)
            if base is not None and base[0] == "inst":
                return self.field_types.get((base[1], node.attr))
            return None
        if isinstance(node, ast.Subscript):
            base = self.type_of(node.value)
            if base is not None and base[0] == "tuple":
                if isinstance(node.slice, ast.Slice):
                    return base
                return ("inst", base[1])
        return None


def check_r1(index: RepoIndex) -> List[Finding]:
    findings: List[Finding] = []
    reached = index.kernel_reachable()

    # (a) 64-bit dtype tokens inside kernel-reachable code
    for modname, qual in sorted(reached):
        mi = index.modules[modname]
        fn = mi.functions[qual]
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute):
                tok = _dtype_token(index, mi, node)
                if tok in WIDE_DTYPES:
                    findings.append(Finding(
                        "R1", mi.path, node.lineno,
                        f"64-bit dtype `{tok}` inside kernel-reachable "
                        f"`{qual}` widens the plan's 32-bit contract",
                        "keep kernel math at int32/float32/uint32; widen "
                        "(if ever) on the host after the dispatch"))

    # (b) pallas out_shape dtypes must stay 32-bit
    for mi in index.modules.values():
        for node in ast.walk(mi.tree):
            if not (isinstance(node, ast.Call)
                    and index.is_external(mi, node.func, SHAPE_DTYPE_STRUCT)):
                continue
            dtype_arg = None
            if len(node.args) >= 2:
                dtype_arg = node.args[1]
            for kw in node.keywords:
                if kw.arg == "dtype":
                    dtype_arg = kw.value
            if dtype_arg is None:
                continue
            tok = _dtype_token(index, mi, dtype_arg)
            if tok in WIDE_DTYPES:
                findings.append(Finding(
                    "R1", mi.path, node.lineno,
                    f"ShapeDtypeStruct declares 64-bit output `{tok}`",
                    "kernel outputs are int32/float32; cast on the host"))

    # (c) silent width drift at the plan builder / kernel boundary:
    #     jnp.asarray(x) without dtype where x is provably 64-bit
    for mi in index.modules.values():
        for qual, fn in mi.functions.items():
            short = qual.rsplit(".", 1)[-1]
            if not (short.startswith("build_") or short.startswith("_pack")
                    or short.startswith("_materialize")):
                continue
            facts = _width_facts(index, mi, fn)
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call)
                        and index.is_external(mi, node.func, "asarray")):
                    continue
                dotted = index.dotted(mi, node.func) or ""
                if not dotted.startswith("jax"):
                    continue
                if any(kw.arg == "dtype" for kw in node.keywords):
                    continue
                if node.args and _width_of(index, mi, node.args[0],
                                           facts) == 64:
                    findings.append(Finding(
                        "R1", mi.path, node.lineno,
                        f"`jnp.asarray` of a 64-bit array in `{short}` "
                        "silently narrows (x64 off) or widens (x64 on) "
                        "the materialized plan field",
                        "cast explicitly: `.astype(np.int32)` (or pass "
                        "dtype=) before handing arrays to jnp"))

    # (d) dead plan fields: materialized by builders, never consumed
    findings.extend(_check_dead_fields(index))
    return findings


def _width_of(index, mi, node, facts) -> Optional[int]:
    if isinstance(node, ast.Name):
        return facts.get(node.id)
    if isinstance(node, ast.Subscript):
        return _width_of(index, mi, node.value, facts)
    if isinstance(node, ast.BinOp):
        return (_width_of(index, mi, node.left, facts)
                or _width_of(index, mi, node.right, facts))
    if isinstance(node, ast.Call):
        func = node.func
        if isinstance(func, ast.Attribute):
            if func.attr == "astype":
                arg = node.args[0] if node.args else None
                tok = _dtype_token(index, mi, arg) if arg is not None else None
                if tok is not None:
                    return 64 if tok.endswith("64") else 32
                return None
            dotted = index.dotted(mi, func) or ""
            if dotted.startswith("numpy."):
                dtype_arg = None
                if func.attr in ("zeros", "full", "arange", "asarray",
                                 "array") and len(node.args) >= 2 \
                        and func.attr in ("zeros",):
                    dtype_arg = node.args[1]
                for kw in node.keywords:
                    if kw.arg == "dtype":
                        dtype_arg = kw.value
                tok = (_dtype_token(index, mi, dtype_arg)
                       if dtype_arg is not None else None)
                if tok is not None:
                    return 64 if tok.endswith("64") else 32
                return None
            # width-preserving methods on a known-width receiver
            if func.attr in ("reshape", "copy", "max", "min", "sum",
                             "transpose", "ravel"):
                return _width_of(index, mi, func.value, facts)
    return None


def _width_facts(index, mi, fn) -> Dict[str, int]:
    facts: Dict[str, int] = {}
    for _ in range(2):
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                w = _width_of(index, mi, node.value, facts)
                if w is not None:
                    facts[node.targets[0].id] = w
    return facts


def _check_dead_fields(index: RepoIndex) -> List[Finding]:
    plans = _plan_classes(index)
    if not plans:
        return []
    field_types: Dict[Tuple[str, str], Tuple[str, str]] = {}
    for cname, fields in plans.items():
        for fname, node in fields.items():
            t = _ann_type(node.annotation, plans)
            if t is not None:
                field_types[(cname, fname)] = t

    consumed: Set[Tuple[str, str]] = set()
    any_names: Set[str] = set()
    for mi in index.modules.values():
        for fn in mi.functions.values():
            typing = _Typing(plans, field_types, fn)
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Attribute)
                        and isinstance(node.ctx, ast.Load)):
                    continue
                base = node.value
                if isinstance(base, ast.Name) and (
                        base.id == "self" or base.id in mi.imports):
                    continue  # internal reads / module attributes
                t = typing.type_of(base)
                if t is not None and t[0] == "inst" and t[1] in plans:
                    if node.attr in plans[t[1]]:
                        consumed.add((t[1], node.attr))
                else:
                    any_names.add(node.attr)

    findings = []
    for cname in sorted(plans):
        fields = plans[cname]
        mi = next(m for m in index.modules.values() if cname in m.classes)
        for fname in fields:
            if (cname, fname) in consumed or fname in any_names:
                continue
            findings.append(Finding(
                "R1", mi.path, fields[fname].lineno,
                f"dead plan field: `{cname}.{fname}` is materialized by "
                "the builder but never consumed by any kernel or driver",
                "drop the field (and its tree_flatten aux slot + builder "
                "kwarg) or wire the consumer that should read it"))
    return findings


# ---------------------------------------------------------------------------
# R2 — window/grid slice safety
# ---------------------------------------------------------------------------


def _pallas_call_sites(index, mi, root):
    """Yield (outer_call, inner_call) for ``pl.pallas_call(...)(...)``."""
    for node in ast.walk(root):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Call) \
                and index.is_external(mi, node.func.func, PALLAS_CALL):
            yield node, node.func


def check_r2(index: RepoIndex) -> List[Finding]:
    findings: List[Finding] = []

    # (a) window packers must guard the cap >= chunk slice-safety invariant
    for mi in index.modules.values():
        for qual, fn in mi.functions.items():
            short = qual.rsplit(".", 1)[-1].lower()
            if not ("pack" in short and "window" in short):
                continue
            guarded = False
            for node in ast.walk(fn):
                if not isinstance(node, ast.If):
                    continue
                has_raise = any(isinstance(s, ast.Raise)
                                for s in ast.walk(node))
                names = {n.id for n in ast.walk(node.test)
                         if isinstance(n, ast.Name)}
                touches = any(("window" in n or "cap" in n or "chunk" in n)
                              for n in names)
                if has_raise and touches:
                    guarded = True
            if not guarded:
                findings.append(Finding(
                    "R2", mi.path, fn.lineno,
                    f"window packer `{qual}` never validates its window "
                    "cap against the chunk width — a cap < chunk makes "
                    "`rel_start + chunk` overrun the window",
                    "raise ValueError when window_cap < chunk before "
                    "packing rows (slice-safety precondition)"))

    # (b) 1-D kernel operands must come from a pad/window producer, so the
    #     kernel's full-chunk dynamic slice is provably in bounds
    for mi in index.modules.values():
        for qual, fn in mi.functions.items():
            safe_names: Set[str] = set()
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Call)):
                    continue
                seg = _last_segment(node.value.func) or ""
                if "pad" in seg.lower() or "window" in seg.lower():
                    for tgt in node.targets:
                        elts = tgt.elts if isinstance(tgt, ast.Tuple) else [tgt]
                        safe_names.update(e.id for e in elts
                                          if isinstance(e, ast.Name))
            for outer, inner in _pallas_call_sites(index, mi, fn):
                specs = None
                for kw in inner.keywords:
                    if kw.arg == "in_specs" and isinstance(kw.value, ast.List):
                        specs = kw.value.elts
                if specs is None:
                    continue
                for i, spec in enumerate(specs):
                    if not (isinstance(spec, ast.Call) and spec.args
                            and isinstance(spec.args[0], ast.Tuple)):
                        continue
                    if len(spec.args[0].elts) != 1:
                        continue  # only flat entry/window operands
                    if i >= len(outer.args):
                        continue
                    arg = outer.args[i]
                    seg = _last_segment(arg) if isinstance(arg, ast.Call) \
                        else None
                    if isinstance(arg, ast.Name) and arg.id in safe_names:
                        continue
                    if seg and ("pad" in seg.lower()
                                or "window" in seg.lower()):
                        continue
                    findings.append(Finding(
                        "R2", mi.path, arg.lineno,
                        f"1-D kernel operand #{i} of the pallas_call in "
                        f"`{qual}` is not derived from a pad/window "
                        "producer — its full-chunk in-kernel slice is not "
                        "provably in bounds",
                        "route the operand through `_pad_entry_rows` "
                        "(lane-row slack) or `windowed_entries` (slice-safe "
                        "window re-layout) before the dispatch"))
    return findings


# ---------------------------------------------------------------------------
# R3 — dispatch accounting
# ---------------------------------------------------------------------------

_ONE, _R, _B, _B0, _BPER = "1", "R", "B", "B0", "Bper"
_SYM_ORDER = (_B, _B0, _R, _BPER, _ONE)


def _merge(*counts: Dict[str, int]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for c in counts:
        for k, v in c.items():
            out[k] = out.get(k, 0) + v
    return {k: v for k, v in out.items() if v}


def _elem_max(a: Dict[str, int], b: Dict[str, int]) -> Dict[str, int]:
    keys = set(a) | set(b)
    return {k: v for k in keys
            if (v := max(a.get(k, 0), b.get(k, 0)))}


def _fmt_sym(c: Dict[str, int]) -> str:
    if not c:
        return "0"
    parts = []
    for k in _SYM_ORDER:
        v = c.get(k, 0)
        if not v:
            continue
        term = k if k != _ONE else ""
        if k == _ONE:
            mag = str(abs(v))
        else:
            mag = k if abs(v) == 1 else f"{abs(v)}*{k}"
        text = mag if k == _ONE else mag
        parts.append(("- " if v < 0 else "+ ") + text)
    joined = " ".join(parts)
    return joined[2:] if joined.startswith("+ ") else "-" + joined[2:]


class _DispatchCounter:
    """Symbolic count of pallas_call dispatches reachable from a function.

    Atoms: 1 (constant), R (len(plan.rounds)), B (total buckets across
    rounds), B0 (round-0 buckets); Bper is the internal per-round bucket
    count a surrounding rounds-loop folds into B. Higher-order parameters
    (``fold_tile=...``, ``fold_round_fn``...) are bound at call sites and
    through callee defaults.
    """

    def __init__(self, index: RepoIndex):
        self.index = index
        self.memo: Dict[tuple, Dict[str, int]] = {}

    # -- function-ref resolution ------------------------------------------

    def _as_func(self, mi: ModuleInfo, cls: Optional[str], bindings,
                 node: ast.AST) -> Optional[Tuple[str, str]]:
        if isinstance(node, ast.Name):
            if node.id in bindings:
                return bindings[node.id]
            if node.id in mi.functions:
                return (mi.name, node.id)
            target = mi.imports.get(node.id)
            if target is not None:
                hit = self.index.resolve_function(target)
                if hit is not None:
                    return (hit[0].name, hit[1])
            return None
        if isinstance(node, ast.Attribute):
            if isinstance(node.value, ast.Name) and node.value.id == "self" \
                    and cls is not None:
                qual = f"{cls}.{node.attr}"
                if qual in mi.functions:
                    return (mi.name, qual)
                return None
            dotted = self.index.dotted(mi, node)
            if dotted is not None:
                hit = self.index.resolve_function(dotted)
                if hit is not None:
                    return (hit[0].name, hit[1])
        return None

    def _bind_call(self, call: ast.Call, caller_mi, caller_cls,
                   caller_bindings, callee: Tuple[str, str]) -> tuple:
        mi = self.index.modules[callee[0]]
        fn = mi.functions[callee[1]]
        args = fn.args
        params = [a.arg for a in args.posonlyargs + args.args]
        if "." in callee[1] and params and params[0] == "self":
            params = params[1:]
        out: Dict[str, Tuple[str, str]] = {}
        # callee defaults (positional tail + kwonly), resolved in its module
        pos = args.posonlyargs + args.args
        for a, d in zip(pos[len(pos) - len(args.defaults):], args.defaults):
            t = self._as_func(mi, None, {}, d)
            if t is not None:
                out[a.arg] = t
        for a, d in zip(args.kwonlyargs, args.kw_defaults):
            if d is not None:
                t = self._as_func(mi, None, {}, d)
                if t is not None:
                    out[a.arg] = t
        for i, arg in enumerate(call.args):
            if i < len(params):
                t = self._as_func(caller_mi, caller_cls, caller_bindings, arg)
                if t is not None:
                    out[params[i]] = t
        for kw in call.keywords:
            if kw.arg is not None:
                t = self._as_func(caller_mi, caller_cls, caller_bindings,
                                  kw.value)
                if t is not None:
                    out[kw.arg] = t
        return tuple(sorted(out.items()))

    # -- counting ----------------------------------------------------------

    def count(self, modname: str, qual: str, bindings: tuple = ()
              ) -> Dict[str, int]:
        key = (modname, qual, bindings)
        if key in self.memo:
            return self.memo[key]
        self.memo[key] = {}  # cycle guard
        mi = self.index.modules[modname]
        fn = mi.functions[qual]
        cls = qual.split(".")[0] if "." in qual else None
        result = self._block(fn.body, mi, cls, dict(bindings), {})
        self.memo[key] = result
        return result

    def _block(self, stmts: Sequence[ast.stmt], mi, cls, bindings,
               env: Dict[str, str]) -> Dict[str, int]:
        total: Dict[str, int] = {}
        for i, stmt in enumerate(stmts):
            if isinstance(stmt, ast.If) and not stmt.orelse \
                    and self._terminates(stmt.body):
                # early-return guard: the rest of the block is the implicit
                # else-arm, so the two paths' dispatches are alternatives
                body = self._block(stmt.body, mi, cls, bindings, dict(env))
                rest = self._block(stmts[i + 1:], mi, cls, bindings, env)
                return _merge(total,
                              self._expr(stmt.test, mi, cls, bindings),
                              _elem_max(body, rest))
            total = _merge(total, self._stmt(stmt, mi, cls, bindings, env))
        return total

    @staticmethod
    def _terminates(stmts: Sequence[ast.stmt]) -> bool:
        return bool(stmts) and isinstance(stmts[-1], (ast.Return, ast.Raise))

    def _stmt(self, stmt, mi, cls, bindings, env) -> Dict[str, int]:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef, ast.Import, ast.ImportFrom,
                             ast.Pass, ast.Global, ast.Nonlocal)):
            return {}
        if isinstance(stmt, ast.For):
            kind, loopvar = self._classify_iter(stmt, env)
            env2 = dict(env)
            if loopvar is not None:
                env2[loopvar] = "roundvar"
            body = self._block(stmt.body, mi, cls, bindings, env2)
            body = _merge(body, self._block(stmt.orelse, mi, cls, bindings,
                                            env2))
            return _merge(self._expr(stmt.iter, mi, cls, bindings),
                          self._xform(body, kind))
        if isinstance(stmt, ast.While):
            return _merge(self._expr(stmt.test, mi, cls, bindings),
                          self._block(stmt.body, mi, cls, bindings, env))
        if isinstance(stmt, ast.If):
            return _merge(
                self._expr(stmt.test, mi, cls, bindings),
                _elem_max(self._block(stmt.body, mi, cls, bindings,
                                      dict(env)),
                          self._block(stmt.orelse, mi, cls, bindings,
                                      dict(env))))
        if isinstance(stmt, ast.With):
            c = _merge(*[self._expr(item.context_expr, mi, cls, bindings)
                         for item in stmt.items]) if stmt.items else {}
            return _merge(c, self._block(stmt.body, mi, cls, bindings, env))
        if isinstance(stmt, ast.Try):
            blocks = [self._block(stmt.body, mi, cls, bindings, env)]
            for h in stmt.handlers:
                blocks.append(self._block(h.body, mi, cls, bindings, env))
            blocks.append(self._block(stmt.orelse, mi, cls, bindings, env))
            blocks.append(self._block(stmt.finalbody, mi, cls, bindings, env))
            return _merge(*blocks)
        if isinstance(stmt, ast.Assign):
            if len(stmt.targets) == 1 and isinstance(stmt.targets[0],
                                                     ast.Name):
                if self._is_round0(stmt.value):
                    env[stmt.targets[0].id] = "round0"
            return self._expr(stmt.value, mi, cls, bindings)
        # Return / Expr / AugAssign / AnnAssign / Assert / Raise / Delete
        c: Dict[str, int] = {}
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, ast.expr):
                c = _merge(c, self._expr(child, mi, cls, bindings))
        return c

    @staticmethod
    def _is_round0(node) -> bool:
        return (isinstance(node, ast.Subscript)
                and isinstance(node.value, ast.Attribute)
                and node.value.attr == "rounds"
                and isinstance(node.slice, ast.Constant)
                and node.slice.value == 0)

    @staticmethod
    def _classify_iter(stmt: ast.For, env) -> Tuple[str, Optional[str]]:
        it, target = stmt.iter, stmt.target
        if isinstance(it, ast.Call) and isinstance(it.func, ast.Name) \
                and it.func.id == "enumerate" and len(it.args) == 1:
            # enumerate(plan.rounds) walks the same rounds
            it = it.args[0]
            if isinstance(target, ast.Tuple) and len(target.elts) == 2:
                target = target.elts[1]
        loopvar = target.id if isinstance(target, ast.Name) else None
        if isinstance(it, ast.Attribute) and it.attr == "rounds":
            return "R", loopvar
        if isinstance(it, ast.Subscript) \
                and isinstance(it.value, ast.Attribute) \
                and it.value.attr == "rounds" \
                and isinstance(it.slice, ast.Slice):
            return "R-1", loopvar
        if isinstance(it, ast.Attribute) and it.attr == "buckets" \
                and isinstance(it.value, ast.Name):
            mark = env.get(it.value.id)
            if mark == "round0":
                return "B0", None
            if mark == "roundvar":
                return "Bper", None
        return "once", None

    @staticmethod
    def _xform(body: Dict[str, int], kind: str) -> Dict[str, int]:
        if kind == "once" or not body:
            return body
        out: Dict[str, int] = {}
        for k, v in body.items():
            if kind == "R":
                tgt = _R if k == _ONE else (_B if k == _BPER else k)
                out[tgt] = out.get(tgt, 0) + v
            elif kind == "R-1":
                if k == _ONE:
                    out[_R] = out.get(_R, 0) + v
                    out[_ONE] = out.get(_ONE, 0) - v
                else:
                    tgt = _B if k == _BPER else k
                    out[tgt] = out.get(tgt, 0) + v
            elif kind == "B0":
                tgt = _B0 if k == _ONE else k
                out[tgt] = out.get(tgt, 0) + v
            elif kind == "Bper":
                tgt = _BPER if k == _ONE else k
                out[tgt] = out.get(tgt, 0) + v
        return {k: v for k, v in out.items() if v}

    def _expr(self, expr, mi, cls, bindings) -> Dict[str, int]:
        total: Dict[str, int] = {}
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            total = _merge(total, self._call(node, mi, cls, bindings))
        return total

    def _call(self, node: ast.Call, mi, cls, bindings) -> Dict[str, int]:
        func = node.func
        if isinstance(func, ast.Call):
            return {}  # pallas_call(...)(...): the inner Call is counted
        if isinstance(func, (ast.Name, ast.Attribute)) \
                and self.index.is_external(mi, func, PALLAS_CALL):
            return {_ONE: 1}
        target = self._as_func(mi, cls, bindings, func)
        if target is None:
            return {}
        callee_bindings = self._bind_call(node, mi, cls, bindings, target)
        return self.count(target[0], target[1], callee_bindings)


def _eval_declared(index, counter, mi, cls, expr) -> Optional[Dict[str, int]]:
    """Evaluate a ``*_dispatches_per_iter`` return expression symbolically."""
    if isinstance(expr, ast.Constant) and isinstance(expr.value, int):
        return {_ONE: expr.value} if expr.value else {}
    if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
        left = _eval_declared(index, counter, mi, cls, expr.left)
        right = _eval_declared(index, counter, mi, cls, expr.right)
        if left is None or right is None:
            return None
        return _merge(left, right)
    if isinstance(expr, ast.Call):
        target = counter._as_func(mi, cls, {}, expr.func)
        if target is None:
            return None
        helper_mi = index.modules[target[0]]
        helper = helper_mi.functions[target[1]]
        for node in ast.walk(helper):
            if isinstance(node, ast.Return) and node.value is not None:
                return _eval_helper_return(node.value)
    return None


def _eval_helper_return(v: ast.AST) -> Optional[Dict[str, int]]:
    if isinstance(v, ast.Constant) and isinstance(v.value, int):
        return {_ONE: v.value} if v.value else {}
    if isinstance(v, ast.Attribute) and v.attr == "n_rounds":
        return {_R: 1}
    # sum(len(r.buckets) for r in plan.rounds)
    if isinstance(v, ast.Call) and _last_segment(v.func) == "sum" \
            and v.args and isinstance(v.args[0], ast.GeneratorExp):
        gen = v.args[0]
        elt = gen.elt
        if isinstance(elt, ast.Call) and _last_segment(elt.func) == "len" \
                and elt.args and isinstance(elt.args[0], ast.Attribute) \
                and elt.args[0].attr == "buckets":
            it = gen.generators[0].iter
            if isinstance(it, ast.Attribute) and it.attr == "rounds":
                return {_B: 1}
    # len(plan.rounds[0].buckets) if plan.rounds else 0
    if isinstance(v, ast.IfExp):
        body = v.body
        if isinstance(body, ast.Call) and _last_segment(body.func) == "len" \
                and body.args and isinstance(body.args[0], ast.Attribute) \
                and body.args[0].attr == "buckets":
            return {_B0: 1}
    return None


#: routable FoldRequest combos -> the family executor each resolves to
#: (``mode`` never changes dispatch counts, so it does not key the table)
_REQUEST_COMBOS = (
    ({"family": "mg", "rescan": False}, "mg_select"),
    ({"family": "bm", "rescan": False}, "bm_fold_plan"),
    ({"family": "mg", "rescan": True}, "mg_rescan"),
)


def _fmt_combo(combo: Dict[str, object]) -> str:
    return ", ".join(f"{k}={v!r}" for k, v in combo.items())


def _request_test(test: ast.AST, combo: Dict[str, object]) -> Optional[bool]:
    """Decide a branch test under a request combo: True/False if the test
    reads only ``request.<field>`` truthiness or (in)equality against a
    constant for fields the combo pins; None when undecidable."""
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        inner = _request_test(test.operand, combo)
        return None if inner is None else (not inner)
    if isinstance(test, ast.Attribute) and isinstance(test.value, ast.Name) \
            and test.value.id == "request" and test.attr in combo:
        return bool(combo[test.attr])
    if isinstance(test, ast.Compare) and len(test.ops) == 1 \
            and len(test.comparators) == 1 \
            and isinstance(test.left, ast.Attribute) \
            and isinstance(test.left.value, ast.Name) \
            and test.left.value.id == "request" \
            and test.left.attr in combo \
            and isinstance(test.comparators[0], ast.Constant):
        eq = combo[test.left.attr] == test.comparators[0].value
        if isinstance(test.ops[0], ast.Eq):
            return eq
        if isinstance(test.ops[0], ast.NotEq):
            return not eq
    return None


def _resolve_request_return(stmts: Sequence[ast.stmt],
                            combo: Dict[str, object]) -> Optional[ast.AST]:
    """The Return expression ``combo`` reaches through the declaration's
    request if-tree; None when an undecidable branch hides a Return."""
    for stmt in stmts:
        if isinstance(stmt, ast.Return):
            return stmt.value
        if isinstance(stmt, ast.If):
            taken = _request_test(stmt.test, combo)
            if taken is None:
                if any(isinstance(n, ast.Return) for n in ast.walk(stmt)):
                    return None
                continue
            ret = _resolve_request_return(
                stmt.body if taken else stmt.orelse, combo)
            if ret is not None:
                return ret
    return None


def check_r3(index: RepoIndex) -> List[Finding]:
    findings: List[Finding] = []
    counter = _DispatchCounter(index)
    for mi in index.modules.values():
        for cname in mi.classes:
            decl = mi.functions.get(f"{cname}.dispatches_per_iter")
            if decl is None or _raise_only(decl):
                continue
            d_args = decl.args
            takes_request = any(
                a.arg == "request"
                for a in d_args.posonlyargs + d_args.args + d_args.kwonlyargs)
            for combo, meas_name in _REQUEST_COMBOS:
                meas = mi.functions.get(f"{cname}.{meas_name}")
                if meas is None or _raise_only(meas):
                    continue
                if takes_request:
                    ret_value = _resolve_request_return(decl.body, combo)
                else:  # legacy single-count declaration: one return for all
                    ret = next((n for n in ast.walk(decl)
                                if isinstance(n, ast.Return)
                                and n.value is not None), None)
                    ret_value = ret.value if ret is not None else None
                if ret_value is None:
                    findings.append(Finding(
                        "R3", mi.path, decl.lineno,
                        f"`{cname}.dispatches_per_iter` has no return "
                        f"kernelcheck can resolve for the request combo "
                        f"({_fmt_combo(combo)})",
                        "branch only on request.family / request.rescan "
                        "(==, !=, truthiness) and return an int literal, "
                        "a sum, or one of the csr.py accounting helpers"))
                    continue
                declared = _eval_declared(index, counter, mi, cname,
                                          ret_value)
                if declared is None:
                    findings.append(Finding(
                        "R3", mi.path, decl.lineno,
                        f"`{cname}.dispatches_per_iter` returns an "
                        f"expression kernelcheck cannot evaluate "
                        f"symbolically for ({_fmt_combo(combo)})",
                        "return an int literal, a sum of literals, or one "
                        "of the csr.py accounting helpers"))
                    continue
                measured = counter.count(mi.name, f"{cname}.{meas_name}")
                if declared != measured:
                    findings.append(Finding(
                        "R3", mi.path, decl.lineno,
                        f"`{cname}.dispatches_per_iter` declares "
                        f"{_fmt_sym(declared)} dispatches/iter for "
                        f"({_fmt_combo(combo)}) but `{meas_name}` reaches "
                        f"{_fmt_sym(measured)} pl.pallas_call sites",
                        "fix the declared count (or remove the stray "
                        "dispatch) so the bench regression gate stays "
                        "honest"))
    return findings


# ---------------------------------------------------------------------------
# R4 — purity of traced code
# ---------------------------------------------------------------------------

_HOST_CASTS = ("float", "int", "bool")
_HOST_METHODS = ("item", "tolist")


def _purity_violations(index, mi, root, where: str) -> List[Finding]:
    findings = []
    for node in ast.walk(root):
        if isinstance(node, ast.Call):
            if isinstance(node.func, ast.Name) \
                    and node.func.id in _HOST_CASTS \
                    and node.func.id not in mi.imports:
                findings.append(Finding(
                    "R4", mi.path, node.lineno,
                    f"host `{node.func.id}()` cast inside {where} forces a "
                    "device sync and breaks tracing",
                    "keep the value traced (jnp ops) or hoist the cast to "
                    "the host wrapper"))
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _HOST_METHODS:
                findings.append(Finding(
                    "R4", mi.path, node.lineno,
                    f"host `.{node.func.attr}()` inside {where}",
                    "traced values cannot be materialized inside a kernel; "
                    "move the readback outside the dispatch"))
        elif isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load) \
                and mi.imports.get(node.id) == "numpy":
            findings.append(Finding(
                "R4", mi.path, node.lineno,
                f"host numpy op inside {where} — np.* does not trace",
                "use jnp/jax.lax inside kernel-reachable code (module-level "
                "np constants that inline as literals are fine)"))
        elif isinstance(node, (ast.If, ast.While)):
            kw = "if" if isinstance(node, ast.If) else "while"
            findings.append(Finding(
                "R4", mi.path, node.lineno,
                f"host `{kw}` branch inside {where} — kernel-reachable "
                "control flow must not branch on traced values",
                "use jnp.where / lax.cond (or hoist static-config branches "
                "to the wrapper before the pallas_call)"))
    return findings


def check_r4(index: RepoIndex) -> List[Finding]:
    findings: List[Finding] = []
    reached = index.kernel_reachable()
    for modname, qual in sorted(reached):
        mi = index.modules[modname]
        fn = mi.functions[qual]
        body = ast.Module(body=fn.body, type_ignores=[])
        findings.extend(_purity_violations(index, mi, body,
                                           f"kernel-reachable `{qual}`"))
    # index_map lambdas inside BlockSpec(...)
    for mi in index.modules.values():
        for node in ast.walk(mi.tree):
            if not (isinstance(node, ast.Call)
                    and index.is_external(mi, node.func, BLOCK_SPEC)):
                continue
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Lambda):
                    findings.extend(_purity_violations(
                        index, mi, arg.body, "an index_map"))
    # mutable default args anywhere in a module that defines kernels
    for mi in index.modules.values():
        if not any(RepoIndex.is_kernel_fn(fn)
                   for fn in mi.functions.values()):
            continue
        for qual, fn in mi.functions.items():
            for d in list(fn.args.defaults) + [d for d in fn.args.kw_defaults
                                               if d is not None]:
                mutable = isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                    isinstance(d, ast.Call)
                    and _last_segment(d.func) in ("list", "dict", "set"))
                if mutable:
                    findings.append(Finding(
                        "R4", mi.path, fn.lineno,
                        f"mutable default argument on `{qual}` in a kernel "
                        "module",
                        "default to None and materialize inside the body"))
    return findings


# ---------------------------------------------------------------------------
# R5 — registry closure
# ---------------------------------------------------------------------------

_FAMILY_TOKENS = {
    "mg": ("mg_candidates", "mg_select", "run_mg_plan"),
    "bm": ("bm_fold_plan", "run_bm_plan"),
    "rescan": ("mg_rescan", "rescan_candidates"),
}


def _registry_engines(mi: ModuleInfo) -> Optional[List[str]]:
    node = mi.module_vars.get("ENGINES")
    if isinstance(node, (ast.Tuple, ast.List)):
        names = [e.value for e in node.elts
                 if isinstance(e, ast.Constant) and isinstance(e.value, str)]
        return names
    return None


def check_r5(index: RepoIndex, tests_dir: Optional[str]) -> List[Finding]:
    findings: List[Finding] = []
    for mi in index.modules.values():
        engines = _registry_engines(mi)
        ge = mi.functions.get("get_engine")
        if engines is None or ge is None:
            continue
        branches: Dict[str, ast.If] = {}
        returned: Dict[str, str] = {}  # engine name -> class name
        for node in ast.walk(ge):
            if not (isinstance(node, ast.If)
                    and isinstance(node.test, ast.Compare)
                    and isinstance(node.test.left, ast.Name)
                    and node.test.left.id == "name"
                    and len(node.test.comparators) == 1
                    and isinstance(node.test.comparators[0], ast.Constant)):
                continue
            bname = node.test.comparators[0].value
            branches[bname] = node
            # the engine class constructed in the branch, seen through any
            # wrapper call (e.g. the checked-contract proxy)
            for sub in ast.walk(node):
                if not (isinstance(sub, ast.Return) and sub.value is not None):
                    continue
                for call in ast.walk(sub.value):
                    if isinstance(call, ast.Call) \
                            and isinstance(call.func, ast.Name) \
                            and call.func.id in mi.classes:
                        returned[bname] = call.func.id
                        break

        # (a) bidirectional ENGINES <-> get_engine branch agreement
        for eng in engines:
            if eng not in branches:
                findings.append(Finding(
                    "R5", mi.path, ge.lineno,
                    f"registry claims backend `{eng}` but get_engine has "
                    "no resolving branch",
                    "add the `if name == ...` branch (or drop the entry "
                    "from ENGINES)"))
        for bname in branches:
            if bname not in engines and bname != "auto":
                findings.append(Finding(
                    "R5", mi.path, branches[bname].lineno,
                    f"get_engine resolves `{bname}` which ENGINES does not "
                    "claim",
                    "add it to ENGINES so callers can discover it (or "
                    "delete the branch)"))

        # (b) every returned engine class overrides the full base surface
        for bname, cls_name in sorted(returned.items()):
            cnode = mi.classes.get(cls_name)
            if cnode is None:
                continue
            for base in cnode.bases:
                base_name = _last_segment(base)
                base_node = mi.classes.get(base_name or "")
                if base_node is None:
                    continue
                for item in base_node.body:
                    if isinstance(item, ast.FunctionDef) \
                            and _raise_only(item) \
                            and f"{cls_name}.{item.name}" not in mi.functions:
                        findings.append(Finding(
                            "R5", mi.path, cnode.lineno,
                            f"engine `{cls_name}` (backend `{bname}`) does "
                            f"not implement `{item.name}` from the engine "
                            "interface",
                            "implement the method — partial engines break "
                            "the uniform (sketch, backend) selection"))

        # (c) engine methods' lazy kernel imports must resolve in-repo
        for cls_name in set(returned.values()):
            cnode = mi.classes.get(cls_name)
            if cnode is None:
                continue
            for node in ast.walk(cnode):
                if not isinstance(node, ast.ImportFrom) or node.level:
                    continue
                mod = node.module or ""
                if mod.split(".")[0] not in index.root_packages:
                    continue
                for alias in node.names:
                    if f"{mod}.{alias.name}" in index.modules:
                        continue
                    target_mi = index.modules.get(mod)
                    defined = target_mi is not None and (
                        alias.name in target_mi.functions
                        or alias.name in target_mi.classes
                        or alias.name in target_mi.module_vars
                        or alias.name in target_mi.imports)
                    if not defined:
                        findings.append(Finding(
                            "R5", mi.path, node.lineno,
                            f"engine `{cls_name}` lazily imports "
                            f"`{mod}.{alias.name}` which does not resolve "
                            "to a kernel in this tree",
                            "fix the import path — the registry must only "
                            "claim backends whose kernels exist"))

        # (d) every claimed non-reference backend has parity fixtures
        if tests_dir and os.path.isdir(tests_dir):
            findings.extend(_check_fixtures(engines, tests_dir, mi))

        # (e) PlanSpec build closure: the declarative plan-build layer must
        # construct plans for every registered backend, and must not build
        # for backends the registry does not claim ("auto" resolves before
        # the branch chain, so it is the one extra name allowed)
        findings.extend(_check_spec_closure(index, engines))
    return findings


def _check_spec_closure(index: RepoIndex, engines: List[str]
                        ) -> List[Finding]:
    findings: List[Finding] = []
    for pmi in index.modules.values():
        if "PlanSpec" not in pmi.classes:
            continue
        bpb = pmi.functions.get("build_plan_bundle")
        if bpb is None:
            continue
        resolved: Set[str] = set()
        for node in ast.walk(bpb):
            if not (isinstance(node, ast.If)
                    and isinstance(node.test, ast.Compare)
                    and isinstance(node.test.left, ast.Name)
                    and node.test.left.id == "backend"
                    and len(node.test.comparators) == 1):
                continue
            comp = node.test.comparators[0]
            if isinstance(comp, ast.Constant) and isinstance(comp.value,
                                                             str):
                resolved.add(comp.value)
            elif isinstance(comp, (ast.Tuple, ast.List)):
                resolved.update(e.value for e in comp.elts
                                if isinstance(e, ast.Constant)
                                and isinstance(e.value, str))
        for eng in engines:
            if eng not in resolved:
                findings.append(Finding(
                    "R5", pmi.path, bpb.lineno,
                    f"registry claims backend `{eng}` but "
                    "build_plan_bundle has no plan-construction branch "
                    "for it — a PlanSpec naming it cannot be built",
                    "add the `backend == ...` branch building the plans "
                    "that engine's requests consume (or drop the entry "
                    "from ENGINES)"))
        for bname in sorted(resolved):
            if bname not in engines and bname != "auto":
                findings.append(Finding(
                    "R5", pmi.path, bpb.lineno,
                    f"build_plan_bundle builds plans for `{bname}` which "
                    "ENGINES does not claim — no get_engine call can ever "
                    "consume them",
                    "add the backend to ENGINES (and a get_engine branch) "
                    "or delete the dead build branch"))
    return findings


def _check_fixtures(engines: List[str], tests_dir: str,
                    mi: ModuleInfo) -> List[Finding]:
    evidence = []  # (path, str constants, identifiers)
    for fname in sorted(os.listdir(tests_dir)):
        if not (fname.startswith("test_") and fname.endswith(".py")):
            continue
        path = os.path.join(tests_dir, fname)
        try:
            with open(path, "r", encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=path)
        except SyntaxError:
            continue
        consts = {n.value for n in ast.walk(tree)
                  if isinstance(n, ast.Constant) and isinstance(n.value, str)}
        idents = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
        idents |= {n.attr for n in ast.walk(tree)
                   if isinstance(n, ast.Attribute)}
        evidence.append((path, consts, idents))

    findings = []
    bundle_tokens = ("build_plan_bundle", "PlanSpec")
    for eng in engines:
        if eng in ("jnp", "auto"):
            continue  # jnp IS the reference oracle
        for family, tokens in _FAMILY_TOKENS.items():
            ok = any(eng in consts and any(t in idents for t in tokens)
                     for _, consts, idents in evidence)
            if not ok:
                findings.append(Finding(
                    "R5", mi.path, 1,
                    f"backend `{eng}` has no `{family}` parity fixture "
                    f"under {tests_dir}/ exercising it by name",
                    "add a test that resolves the engine via get_engine "
                    "and bit-compares against the jnp reference"))
        # fixture closure keyed on the plan-build layer: every backend a
        # PlanSpec can name needs a golden plan-equality fixture
        ok = any(eng in consts and any(t in idents for t in bundle_tokens)
                 for _, consts, idents in evidence)
        if not ok:
            findings.append(Finding(
                "R5", mi.path, 1,
                f"backend `{eng}` has no plan-bundle golden fixture under "
                f"{tests_dir}/ building it through build_plan_bundle",
                "add a golden plan-equality test keyed on PlanSpec "
                "(build_plan_bundle output vs the csr.py builders)"))
    return findings


# ---------------------------------------------------------------------------
# R6 — aligned-layout gather accounting
# ---------------------------------------------------------------------------

#: the O(|E|) windowed re-layout gather an aligned round makes redundant
_RELAYOUT_GATHER = "windowed_entries"
#: the per-iteration gather accounting helper the benchmarks report
_GATHER_ACCOUNTING = "streamed_gather_slots"


def _mentions_aligned(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and "aligned" in sub.attr:
            return True
        if isinstance(sub, ast.Name) and "aligned" in sub.id:
            return True
    return False


def check_r6(index: RepoIndex) -> List[Finding]:
    """Window-aligned rounds must skip the re-layout gather, and the
    gather accounting must declare that skip.

    (a) every call to the windowed re-layout gather must sit under a
        conditional testing an ``aligned`` flag — an unguarded call
        re-pays the O(|E|) HBM round-trip on rounds whose entries were
        already materialized window-aligned at plan build time;
    (b) the ``streamed_gather_slots`` accounting helper must exclude
        aligned rounds, so aligned plans *declare* the reduced gather
        count the bench traffic columns and DESIGN.md §13 promise.
    """
    findings: List[Finding] = []
    for mi in index.modules.values():
        for qual, fn in mi.functions.items():
            short = qual.rsplit(".", 1)[-1]
            if short == _RELAYOUT_GATHER:
                continue  # the producer itself, not a consumer
            # call nodes lexically under an `aligned`-testing conditional
            guarded: Set[int] = set()
            for node in ast.walk(fn):
                if isinstance(node, (ast.If, ast.IfExp)) \
                        and _mentions_aligned(node.test):
                    guarded.update(id(sub) for sub in ast.walk(node))
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call)
                        and _last_segment(node.func) == _RELAYOUT_GATHER):
                    continue
                if id(node) not in guarded:
                    findings.append(Finding(
                        "R6", mi.path, node.lineno,
                        f"`{qual}` re-lays entries through "
                        f"`{_RELAYOUT_GATHER}` unconditionally — an "
                        "aligned round's entries are already in window "
                        "order, so this re-pays the O(|E|) HBM gather the "
                        "aligned layout removes",
                        "branch on the round's `aligned` flag and take the "
                        "pre-windowed arrays directly when it is set"))
            if short == _GATHER_ACCOUNTING:
                tests = [n.test for n in ast.walk(fn)
                         if isinstance(n, (ast.If, ast.IfExp))]
                for comp in ast.walk(fn):
                    if isinstance(comp, ast.comprehension):
                        tests.extend(comp.ifs)
                if not any(_mentions_aligned(t) for t in tests):
                    findings.append(Finding(
                        "R6", mi.path, fn.lineno,
                        f"`{qual}` counts every round's window slots — "
                        "aligned rounds gather nothing, so aligned plans "
                        "must declare the reduced count",
                        "filter rounds on `not r.aligned` so the bench "
                        "traffic columns stay honest"))
    return findings


# ---------------------------------------------------------------------------
# R7 — request-routing closure
# ---------------------------------------------------------------------------


def _reachable_nodes(stmts: Sequence[ast.stmt],
                     combo: Dict[str, object]) -> List[ast.AST]:
    """Nodes ``combo`` can reach through a router body: a decidable
    request test prunes its dead arm, everything else (tests included)
    stays reachable — conservative in the clean direction."""
    out: List[ast.AST] = []
    for stmt in stmts:
        if isinstance(stmt, ast.If):
            out.append(stmt.test)
            taken = _request_test(stmt.test, combo)
            if taken is None or taken:
                out.extend(_reachable_nodes(stmt.body, combo))
            if taken is None or not taken:
                out.extend(_reachable_nodes(stmt.orelse, combo))
        else:
            out.append(stmt)
    return out


def _is_self_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    func = node.func
    while isinstance(func, ast.Attribute):
        func = func.value
    return isinstance(func, ast.Name) and func.id == "self"


def check_r7(index: RepoIndex) -> List[Finding]:
    """Request-routing closure: every ``run(...)`` that routes a request
    must reach an executor (a ``self.*`` call — a family method, or an
    unconditional delegate like a wrapper's ``self._inner.run``) for
    every routable request combo. A combo that silently falls off the
    routing table returns garbage instead of raising."""
    findings: List[Finding] = []
    for mi in index.modules.values():
        for cname in mi.classes:
            run = mi.functions.get(f"{cname}.run")
            if run is None or _raise_only(run):
                continue
            r_args = run.args
            params = [a.arg for a in r_args.posonlyargs + r_args.args
                      + r_args.kwonlyargs]
            if "request" not in params:
                continue
            for combo, _ in _REQUEST_COMBOS:
                reachable = _reachable_nodes(run.body, combo)
                routed = any(_is_self_call(sub)
                             for node in reachable
                             for sub in ast.walk(node))
                if not routed:
                    findings.append(Finding(
                        "R7", mi.path, run.lineno,
                        f"`{cname}.run` routes no executor for the request "
                        f"combo ({_fmt_combo(combo)}) — the combo falls "
                        "off the routing table",
                        "route every FoldRequest combo to a family "
                        "executor (or reject it in the request's "
                        "__post_init__ so it cannot be built)"))
    return findings


# ---------------------------------------------------------------------------
# R8 — dead bundle fields
# ---------------------------------------------------------------------------

#: bundle container classes are dataclasses named *Bundle — the build-time
#: counterpart of the *Plan/*Round/*Bucket containers R1 covers
_BUNDLE_SUFFIXES = ("Bundle",)
#: pytree plumbing: reads here keep a field alive structurally without a
#: real consumer, so they do not count as consumption
_PYTREE_METHODS = ("tree_flatten", "tree_unflatten")


def check_r8(index: RepoIndex) -> List[Finding]:
    """Dead bundle fields: every field of a ``*Bundle`` dataclass must be
    consumed by an attribute read outside the pytree plumbing
    (``tree_flatten``/``tree_unflatten``). R1's dead-plan-field rule,
    generalized to the plan-build layer: a field only the
    flatten/unflatten round-trip touches rides every bundle for nothing.
    Unlike R1, ``self.<field>`` reads inside the bundle's own methods DO
    count — the shared sizing policy lives on the bundle."""
    bundles = _field_classes(index, _BUNDLE_SUFFIXES)
    if not bundles:
        return []
    # type through plan AND bundle classes so e.g. `bundle.plan.rounds`
    # resolves the same way R1's receiver typing does
    classes = {**_plan_classes(index), **bundles}
    field_types: Dict[Tuple[str, str], Tuple[str, str]] = {}
    for cname, fields in classes.items():
        for fname, node in fields.items():
            t = _ann_type(node.annotation, classes)
            if t is not None:
                field_types[(cname, fname)] = t

    consumed: Set[Tuple[str, str]] = set()
    any_names: Set[str] = set()
    for mi in index.modules.values():
        for qual, fn in mi.functions.items():
            if qual.rsplit(".", 1)[-1] in _PYTREE_METHODS:
                continue
            cls = qual.split(".")[0] if "." in qual else None
            typing = _Typing(classes, field_types, fn)
            if cls in bundles:
                typing.env.setdefault("self", ("inst", cls))
                # re-propagate so locals assigned from self.* fields type
                for node in ast.walk(fn):
                    if isinstance(node, ast.Assign) \
                            and len(node.targets) == 1 \
                            and isinstance(node.targets[0], ast.Name):
                        t = typing.type_of(node.value)
                        if t is not None:
                            typing.env[node.targets[0].id] = t
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Attribute)
                        and isinstance(node.ctx, ast.Load)):
                    continue
                base = node.value
                if isinstance(base, ast.Name) and base.id in mi.imports:
                    continue  # module attributes
                t = typing.type_of(base)
                if t is not None and t[0] == "inst" and t[1] in bundles:
                    if node.attr in bundles[t[1]]:
                        consumed.add((t[1], node.attr))
                elif not (isinstance(base, ast.Name) and base.id == "self"):
                    any_names.add(node.attr)

    findings = []
    for cname in sorted(bundles):
        fields = bundles[cname]
        mi = next(m for m in index.modules.values() if cname in m.classes)
        for fname in fields:
            if (cname, fname) in consumed or fname in any_names:
                continue
            findings.append(Finding(
                "R8", mi.path, fields[fname].lineno,
                f"dead bundle field: `{cname}.{fname}` is materialized by "
                "the plan-build layer but never consumed outside the "
                "pytree plumbing",
                "drop the field (and its tree_flatten slot) or wire the "
                "engine/driver lookup that should key off it"))
    return findings


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_all(index: RepoIndex, tests_dir: Optional[str] = None
            ) -> List[Finding]:
    findings: List[Finding] = []
    findings.extend(check_r1(index))
    findings.extend(check_r2(index))
    findings.extend(check_r3(index))
    findings.extend(check_r4(index))
    findings.extend(check_r5(index, tests_dir))
    findings.extend(check_r6(index))
    findings.extend(check_r7(index))
    findings.extend(check_r8(index))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
