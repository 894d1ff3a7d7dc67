"""Generated-code pass: AST-level analysis of emitted kernel sources.

All emitters (:class:`repro.core.codegen.JaxCodeGenerator` and the
:mod:`repro.core.pallasgen` generators) produce Python source
that is ``exec``'d and shipped. This pass parses that source with
:mod:`ast` and checks it against the *declared* program geometry —
defects here escape the exec round-trip (Python compiles ``x[999]``
happily) and only explode inside ``pallas_call`` or, worse, silently
read the wrong tile:

* **out-of-bounds tile indexing** (``error``) — constant indices vs the
  declared :class:`~repro.core.dsl.ArraySpec` shape, through the alias
  chain (``_v3 = x`` / ``_v3 = x_ref[...]`` carry x's shape), including
  ``arr.at[i].set(v)`` stores and rank overflow;
* **use-before-def** (``error``) — a name read before any binding, with
  closure semantics for nested loop bodies (``def _loopN`` may read
  anything its enclosing function ever binds, since it runs at
  ``fori_loop`` time);
* **ref aliasing** (``warning``) — ``inout`` arrays are bound to both
  ``{a}_ref`` and ``{a}_oref`` over the same buffer: reading the
  ``_ref`` after the ``_oref`` was written observes the new value;
* **overwritten stores** (``warning``) — two writes to one ``_oref``
  with the same static index and no intervening read of that array;
* **dead loads** (``warning``) — a ``_vN`` load temp never consumed;
* **memory-access order** (``info``) — the overlap-distance lint: loads
  whose first consumer is the immediately following statement leave the
  scheduler no latency to hide (one aggregated note per function).
"""
from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from .findings import PASS_CODEGEN, Finding

Shape = Optional[Tuple[Optional[int], ...]]

_TEMP_RE = re.compile(r"_v\d+$")
_GLOBALS = {
    "jax", "jnp", "lax", "_rothalf", "_calls",
    "True", "False", "None", "range", "len", "float", "int", "tuple",
}


def shapes_of(prog) -> Dict[str, Shape]:
    """Declared shapes of a :class:`~repro.core.dsl.KernelProgram`."""
    return {name: spec.shape for name, spec in prog.arrays.items()}


def _base_array(name: str, shapes: Dict[str, Shape]) -> Optional[str]:
    """Resolve a source identifier to a declared array (Pallas refs
    strip their ``_ref``/``_oref`` suffix)."""
    if name in shapes:
        return name
    for suf in ("_oref", "_ref"):
        if name.endswith(suf) and name[: -len(suf)] in shapes:
            return name[: -len(suf)]
    return None


def _sub_base(node: ast.expr) -> Optional[str]:
    """Identifier a subscript indexes: ``x[...]`` or ``x.at[...]``."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute) and node.attr == "at" and \
            isinstance(node.value, ast.Name):
        return node.value.id
    return None


def _const_int(node: ast.expr) -> Optional[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        inner = _const_int(node.operand)
        return None if inner is None else -inner
    return None


def _strip_cast(node: ast.expr) -> ast.expr:
    """``x.astype(dt)`` -> ``x``: the Pallas emitters upcast every load
    and cast every store, which changes neither aliasing nor shape."""
    while (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
           and node.func.attr == "astype"):
        node = node.func.value
    return node


def _index_elts(sub: ast.Subscript) -> List[ast.expr]:
    sl = sub.slice
    return list(sl.elts) if isinstance(sl, ast.Tuple) else [sl]


def _is_ellipsis(node: ast.expr) -> bool:
    return isinstance(node, ast.Constant) and node.value is Ellipsis


def check_generated(source: str, shapes: Dict[str, Shape], *,
                    subject: str = "") -> List[Finding]:
    """Analyze one emitted kernel source against declared ``shapes``."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding(PASS_CODEGEN, "error", "syntax-error",
                        f"emitted source does not parse: {e}",
                        subject=subject)]
    out: List[Finding] = []
    module_fns = {n.name for n in tree.body
                  if isinstance(n, ast.FunctionDef)}
    for fn in tree.body:
        # the prelude's _rothalf helper is not generated code
        if isinstance(fn, ast.FunctionDef) and fn.name != "_rothalf":
            tag = subject or fn.name
            out.extend(_check_fn(fn, shapes, module_fns, tag))
    return out


# -- per-function analysis ----------------------------------------------------
def _assigned_names(stmts: List[ast.stmt]) -> Set[str]:
    """Every name a statement list binds, at any nesting depth."""
    out: Set[str] = set()
    for st in stmts:
        for node in ast.walk(st):
            if isinstance(node, ast.Name) and \
                    isinstance(node.ctx, ast.Store):
                out.add(node.id)
            elif isinstance(node, ast.FunctionDef):
                out.add(node.name)
                out.update(a.arg for a in node.args.args)
    return out


def _loads_outside_nested(st: ast.stmt) -> List[ast.Name]:
    """Name loads of one statement, excluding nested-function bodies
    (those are checked with closure semantics separately)."""
    found: List[ast.Name] = []

    def walk(node: ast.AST):
        if isinstance(node, ast.FunctionDef) and node is not st:
            return
        if isinstance(node, ast.Attribute) and node.attr == "dtype":
            return    # ref metadata (the store cast), not a read
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            found.append(node)
        for ch in ast.iter_child_nodes(node):
            walk(ch)
    walk(st)
    return found


def _shape_env(fn: ast.FunctionDef,
               shapes: Dict[str, Shape]) -> Dict[str, Shape]:
    """Known static shapes per identifier: declared arrays, their
    Pallas refs, and whole-value aliases (``_v3 = x`` /
    ``_v3 = x_ref[...]`` / ``o = x.at[i].set(v)``)."""
    env: Dict[str, Shape] = {}
    for name, shp in shapes.items():
        env[name] = shp
        env[f"{name}_ref"] = shp
        env[f"{name}_oref"] = shp
    changed = True
    while changed:                       # aliases of aliases
        changed = False
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                continue
            tgt = node.targets[0].id
            if tgt in env:
                continue
            src: Optional[str] = None
            val = _strip_cast(node.value)
            if isinstance(val, ast.Name):
                src = val.id             # _v3 = x
            elif isinstance(val, ast.Subscript) and \
                    len(_index_elts(val)) == 1 and \
                    _is_ellipsis(_index_elts(val)[0]):
                src = _sub_base(val.value)   # _v3 = x_ref[...]
            elif isinstance(val, ast.Call) and \
                    isinstance(val.func, ast.Attribute) and \
                    val.func.attr == "set" and \
                    isinstance(val.func.value, ast.Subscript):
                src = _sub_base(val.func.value.value)  # o = x.at[i].set(v)
            if src is not None and src in env:
                env[tgt] = env[src]
                changed = True
    return env


def _check_fn(fn: ast.FunctionDef, shapes: Dict[str, Shape],
              module_fns: Set[str], tag: str) -> List[Finding]:
    out: List[Finding] = []
    env = _shape_env(fn, shapes)

    # ---- out-of-bounds / rank check over every subscript ------------------
    for node in ast.walk(fn):
        if not isinstance(node, ast.Subscript):
            continue
        base = _sub_base(node.value)
        shp = env.get(base) if base is not None else None
        if shp is None:
            continue
        elts = _index_elts(node)
        if len(elts) == 1 and _is_ellipsis(elts[0]):
            continue                      # whole-tile ref access
        if len(elts) > len(shp):
            out.append(Finding(
                PASS_CODEGEN, "error", "rank-mismatch",
                f"{base} has rank {len(shp)} but is indexed with "
                f"{len(elts)} subscripts", subject=f"{tag}:{base}"))
            continue
        for dim, (elt, extent) in enumerate(zip(elts, shp)):
            idx = _const_int(elt)
            if idx is None or extent is None:
                continue                  # dynamic index / symbolic dim
            if not (-extent <= idx < extent):
                out.append(Finding(
                    PASS_CODEGEN, "error", "oob-index",
                    f"constant index {idx} out of bounds for {base} "
                    f"dim {dim} (extent {extent})",
                    subject=f"{tag}:{base}"))

    # ---- use-before-def (closure-aware) -----------------------------------
    def scan(stmts: List[ast.stmt], defined: Set[str], closure: Set[str]):
        for st in stmts:
            if isinstance(st, ast.FunctionDef):
                # body runs later: it may read anything the enclosing
                # scope ever binds (fori_loop carries, later temps)
                inner = set(a.arg for a in st.args.args)
                scan(st.body, inner,
                     closure | defined | _assigned_names(stmts))
                defined.add(st.name)
                continue
            for nm in _loads_outside_nested(st):
                name = nm.id
                if name in defined or name in closure or \
                        name in _GLOBALS or name in module_fns:
                    continue
                out.append(Finding(
                    PASS_CODEGEN, "error", "use-before-def",
                    f"{name!r} is read at line {nm.lineno} before any "
                    f"definition", subject=f"{tag}:{name}"))
                defined.add(name)        # report each name once
            for node in ast.walk(st):
                if isinstance(node, ast.Name) and \
                        isinstance(node.ctx, ast.Store):
                    defined.add(node.id)

    scan(fn.body, {a.arg for a in fn.args.args}, set())

    # ---- linear top-level walk: stores, aliasing, dead loads, overlap -----
    stmts = fn.body
    all_loads: Dict[str, List[int]] = {}     # name -> stmt positions read
    load_defs: Dict[str, int] = {}           # _vN load temp -> position
    writes: Dict[str, List[Tuple[int, str]]] = {}  # array -> (pos, idx repr)
    reads_of_array: Dict[str, List[int]] = {}
    first_oref_write: Dict[str, int] = {}

    for pos, st in enumerate(stmts):
        for nm in _loads_outside_nested(st) + [
                n for f_ in ast.walk(st) if isinstance(f_, ast.FunctionDef)
                for n in ast.walk(f_)
                if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)]:
            all_loads.setdefault(nm.id, []).append(pos)
            base = _base_array(nm.id, shapes)
            if base is not None:
                reads_of_array.setdefault(base, []).append(pos)
        if not isinstance(st, ast.Assign) or len(st.targets) != 1:
            continue
        tgt = st.targets[0]
        if isinstance(tgt, ast.Subscript):            # x_oref[...] = v
            ref = _sub_base(tgt.value)
            base = _base_array(ref, shapes) if ref else None
            if base is not None:
                writes.setdefault(base, []).append(
                    (pos, ast.dump(tgt.slice)))
                if ref and ref.endswith("_oref"):
                    first_oref_write.setdefault(base, pos)
        elif isinstance(tgt, ast.Name) and _TEMP_RE.match(tgt.id):
            val = _strip_cast(st.value)
            is_load = (isinstance(val, ast.Name)
                       and _base_array(val.id, shapes) is not None) or \
                      (isinstance(val, ast.Subscript)
                       and _sub_base(val.value) is not None
                       and _base_array(_sub_base(val.value), shapes)
                       is not None)
            if is_load:
                load_defs[tgt.id] = pos

    # inout aliasing: _ref read after the aliased _oref was written
    for base, wpos in first_oref_write.items():
        ref_reads = [p for p in all_loads.get(f"{base}_ref", [])
                     if p > wpos]
        if ref_reads:
            out.append(Finding(
                PASS_CODEGEN, "warning", "aliased-read-after-write",
                f"{base}_ref is read at statement {ref_reads[0]} after "
                f"{base}_oref was written at statement {wpos} — inout "
                f"refs alias one buffer", subject=f"{tag}:{base}"))

    # overwritten stores: same static index, no intervening read
    for base, ws in writes.items():
        for (p1, i1), (p2, i2) in zip(ws, ws[1:]):
            if i1 != i2:
                continue
            between = [p for p in reads_of_array.get(base, [])
                       if p1 < p <= p2]
            if not between:
                out.append(Finding(
                    PASS_CODEGEN, "warning", "overwritten-store",
                    f"store to {base} at statement {p1} is overwritten "
                    f"at {p2} with no intervening read",
                    subject=f"{tag}:{base}"))

    # dead loads + overlap-distance lint
    zero_overlap = 0
    for name, pos in load_defs.items():
        later = [p for p in all_loads.get(name, []) if p > pos]
        if not later:
            out.append(Finding(
                PASS_CODEGEN, "warning", "dead-load",
                f"load temp {name} (statement {pos}) is never read",
                subject=f"{tag}:{name}"))
        elif later[0] == pos + 1:
            zero_overlap += 1
    if zero_overlap:
        out.append(Finding(
            PASS_CODEGEN, "info", "zero-overlap-load",
            f"{zero_overlap} of {len(load_defs)} loads are consumed by "
            f"the immediately following statement (no latency-hiding "
            f"distance)", subject=tag))

    return out
