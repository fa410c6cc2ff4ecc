"""Expression evaluation on device arrays.

Filters and join-key computations run as jnp vector ops (VPU work under XLA). String
semantics ride the sorted-dictionary encoding: literal comparisons are translated to
code-space integer comparisons on the host (one dictionary binary-search per literal),
then evaluated on device — no string processing ever reaches the TPU.

Null semantics (SQL/Spark parity) ride a VALIDITY LANE: every evaluation result
carries an optional device bool array marking which slots are non-null. Comparisons
and arithmetic propagate invalidity; AND/OR use Kleene logic (FALSE dominates AND,
TRUE dominates OR); `evaluate_predicate` finally keeps a row only if the value is
true AND valid — a comparison with null is "unknown", and WHERE drops unknowns.
`valid=None` means all-valid, keeping the null-free fast path branch-free.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from ..exceptions import HyperspaceException
from .expr import BinaryOp, Col, Expr, IsIn, IsNull, Lit, Not, Udf
from .device_cache import device_array
from .table import Column, Table, align_dictionaries


class _Val:
    """Evaluation result: numeric device array, string codes + dictionary, or
    literal — plus the validity lane (None = all valid)."""

    __slots__ = ("kind", "arr", "dictionary", "value", "valid")

    def __init__(self, kind, arr=None, dictionary=None, value=None, valid=None):
        self.kind = kind  # "num" | "str" | "lit"
        self.arr = arr
        self.dictionary = dictionary
        self.value = value
        self.valid = valid  # device bool array, or None


def _and_valid(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return jnp.logical_and(a, b)


def _device(table: Table, devcols: Dict[str, jnp.ndarray], name: str):
    if name not in devcols:
        col = table.column(name)
        if col.is_string:
            # Qualifying string columns UPLOAD narrow dictionary codes and
            # widen on device (encoded_device.py): H2D moves the compressed
            # lane, downstream code-space ops keep seeing int32.
            from .encoded_device import stage_codes

            arr = stage_codes(col, "eval_pred")
            if arr.dtype != jnp.int32:
                arr = arr.astype(jnp.int32)
            devcols[name] = arr
        else:
            devcols[name] = device_array(col.data)
    return devcols[name]


def _col_valid(table: Table, devcols: Dict[str, jnp.ndarray], name: str):
    col = table.column(name)
    if col.validity is None:
        return None
    key = f"__valid__{name}"
    if key not in devcols:
        devcols[key] = device_array(col.validity)
    return devcols[key]


def _str_lit_compare(op: str, codes, dictionary: np.ndarray, lit: str):
    """Translate a string-vs-literal comparison into code space (sorted dictionary ⇒
    codes are order-preserving)."""
    left_cut = int(np.searchsorted(dictionary, lit, side="left"))
    present = left_cut < len(dictionary) and dictionary[left_cut] == lit
    if op == "==":
        if not present:
            return jnp.zeros(codes.shape, dtype=bool)
        return codes == left_cut
    if op == "!=":
        if not present:
            return jnp.ones(codes.shape, dtype=bool)
        return codes != left_cut
    if op == "<":
        return codes < left_cut
    if op == ">=":
        return codes >= left_cut
    right_cut = int(np.searchsorted(dictionary, lit, side="right"))
    if op == "<=":
        return codes < right_cut
    if op == ">":
        return codes >= right_cut
    raise HyperspaceException(f"Unsupported string comparison: {op}")


def evaluate(expr: Expr, table: Table, devcols: Dict[str, jnp.ndarray]) -> _Val:
    if isinstance(expr, Col):
        col = table.column(expr.name)
        arr = _device(table, devcols, expr.name)
        valid = _col_valid(table, devcols, expr.name)
        if col.is_string:
            return _Val("str", arr, col.dictionary, valid=valid)
        return _Val("num", arr, valid=valid)

    if isinstance(expr, Lit):
        return _Val("lit", value=expr.value)

    if isinstance(expr, IsNull):
        v = evaluate(expr.child, table, devcols)
        if v.kind == "lit":
            is_null = v.value is None
            n = table.num_rows
            base = jnp.full((n,), is_null, dtype=bool)
        elif v.valid is None:
            base = jnp.zeros(v.arr.shape, dtype=bool)
        else:
            base = jnp.logical_not(v.valid)
        if expr.negated:
            base = jnp.logical_not(base)
        return _Val("num", base)  # IS [NOT] NULL is never itself null

    if isinstance(expr, Not):
        v = evaluate(expr.child, table, devcols)
        if v.kind != "num":
            raise HyperspaceException("NOT requires a boolean operand")
        return _Val("num", jnp.logical_not(v.arr), valid=v.valid)

    if isinstance(expr, IsIn):
        v = evaluate(expr.child, table, devcols)
        # Kleene: `x IN (v1, NULL)` is TRUE on match, else UNKNOWN (never FALSE) —
        # so NOT(... IN (.., NULL)) must drop non-matching rows, like SQL/Spark.
        had_null = any(x is None for x in expr.values)
        values = [x for x in expr.values if x is not None]
        if v.kind == "str":
            wanted = [str(x) for x in values]
            positions = np.searchsorted(v.dictionary, wanted)
            hits = [
                int(c)
                for c, x in zip(positions, wanted)
                if c < len(v.dictionary) and v.dictionary[c] == x
            ]
            if not hits:
                match = jnp.zeros(v.arr.shape, dtype=bool)
            else:
                match = jnp.isin(v.arr, jnp.asarray(np.asarray(hits, np.int32)))
        else:
            if not values:
                match = jnp.zeros(v.arr.shape, dtype=bool)
            else:
                match = jnp.isin(v.arr, jnp.asarray(np.asarray(values)))
        valid = v.valid
        if had_null:
            valid = _and_valid(valid, match)
        return _Val("num", match, valid=valid)

    if isinstance(expr, Udf):
        return _evaluate_udf(expr, table, devcols)

    if isinstance(expr, BinaryOp):
        l = evaluate(expr.left, table, devcols)
        r = evaluate(expr.right, table, devcols)
        op = expr.op

        if op in BinaryOp.BOOLEAN:
            if l.kind != "num" or r.kind != "num":
                raise HyperspaceException(f"'{op}' requires boolean operands")
            lv, rv = l.arr, r.arr
            if op == "and":
                value = jnp.logical_and(lv, rv)
                if l.valid is None and r.valid is None:
                    valid = None
                else:
                    # Kleene: known iff both known, or either side is a known FALSE.
                    lk = l.valid if l.valid is not None else jnp.ones(lv.shape, bool)
                    rk = r.valid if r.valid is not None else jnp.ones(rv.shape, bool)
                    valid = (lk & rk) | (lk & ~lv) | (rk & ~rv)
            else:
                value = jnp.logical_or(lv, rv)
                if l.valid is None and r.valid is None:
                    valid = None
                else:
                    # Kleene: known iff both known, or either side is a known TRUE.
                    lk = l.valid if l.valid is not None else jnp.ones(lv.shape, bool)
                    rk = r.valid if r.valid is not None else jnp.ones(rv.shape, bool)
                    valid = (lk & rk) | (lk & lv) | (rk & rv)
            return _Val("num", value, valid=valid)

        # A null literal compares unknown against everything.
        if (l.kind == "lit" and l.value is None) or (r.kind == "lit" and r.value is None):
            n = table.num_rows
            return _Val(
                "num", jnp.zeros((n,), dtype=bool), valid=jnp.zeros((n,), dtype=bool)
            )

        valid = _and_valid(l.valid, r.valid)

        # String comparisons.
        if l.kind == "str" or r.kind == "str":
            if op not in BinaryOp.COMPARISONS:
                raise HyperspaceException(f"Arithmetic on strings is not supported: {op}")
            if l.kind == "str" and r.kind == "lit":
                return _Val(
                    "num", _str_lit_compare(op, l.arr, l.dictionary, str(r.value)), valid=valid
                )
            if r.kind == "str" and l.kind == "lit":
                flipped = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "==": "==", "!=": "!="}
                return _Val(
                    "num",
                    _str_lit_compare(flipped[op], r.arr, r.dictionary, str(l.value)),
                    valid=valid,
                )
            if l.kind == "str" and r.kind == "str":
                # Cross-column compare: align over the union dictionary (host), then
                # integer-compare codes on device.
                lc = Column("string", np.asarray(l.arr, dtype=np.int32), l.dictionary)
                rc = Column("string", np.asarray(r.arr, dtype=np.int32), r.dictionary)
                la, ra = align_dictionaries(lc, rc)
                return _Val(
                    "num",
                    _compare(op, jnp.asarray(la.data), jnp.asarray(ra.data)),
                    valid=valid,
                )
            raise HyperspaceException("Cannot compare string with non-string")

        lv = l.arr if l.kind == "num" else jnp.asarray(l.value)
        rv = r.arr if r.kind == "num" else jnp.asarray(r.value)
        if op in BinaryOp.COMPARISONS:
            return _Val("num", _compare(op, lv, rv), valid=valid)
        if op == "+":
            return _Val("num", lv + rv, valid=valid)
        if op == "-":
            return _Val("num", lv - rv, valid=valid)
        if op == "*":
            return _Val("num", lv * rv, valid=valid)
        if op == "/":
            # SQL: x / 0 is NULL, not inf/nan — zero divisors go invalid.
            zero = rv == 0
            safe = jnp.where(zero, jnp.ones_like(rv), rv)
            value = lv / safe
            nonzero = jnp.broadcast_to(~zero, value.shape)
            valid = nonzero if valid is None else (valid & nonzero)
            return _Val("num", jnp.where(nonzero, value, 0.0), valid=valid)

    raise HyperspaceException(f"Cannot evaluate expression: {expr!r}")


def _evaluate_udf(expr: Udf, table, devcols: Dict[str, jnp.ndarray]) -> _Val:
    """HOST evaluation of a user-defined function (the documented contract of
    `expr.Udf`): argument values are pulled to the host, strings decoded, null
    slots delivered as None; the function runs row-wise in Python; the result
    is packaged back under the DECLARED dtype with None → null."""
    n = table.num_rows
    prepared = []
    for a in expr.args:
        if isinstance(a, Col) and isinstance(table, Table):
            # Column args read straight from host storage — round-tripping
            # them through the device (evaluate's _device upload + the pull
            # below) would cost two full-column transfers for host-only work.
            c = table.column(a.name)
            data = c.dictionary[c.data] if c.is_string else c.data
            prepared.append(("arr", data, c.validity))
            continue
        v = evaluate(a, table, devcols)
        if v.kind == "lit":
            prepared.append(("lit", v.value, None))
            continue
        valid = None if v.valid is None else np.asarray(v.valid, bool)
        if v.kind == "str":
            data = np.asarray(v.dictionary)[np.asarray(v.arr)]
        else:
            data = np.asarray(v.arr)
        if data.ndim == 0:
            # Literal arithmetic yields 0-d results (the same case
            # evaluate_column broadcasts): treat as a per-row constant.
            prepared.append(("lit", data.item(), None))
            continue
        if valid is not None and valid.ndim == 0:
            valid = np.full(data.shape, bool(valid))
        prepared.append(("arr", data, valid))
    out = []
    for i in range(n):
        args = []
        for kind, data, valid in prepared:
            if kind == "lit":
                args.append(data)
            elif valid is not None and not valid[i]:
                args.append(None)
            else:
                x = data[i]
                args.append(x.item() if hasattr(x, "item") else x)
        out.append(expr.fn(*args))
    if expr.dtype == "string":
        if n == 0:
            # from_values can't infer stringness from an empty object array.
            return _Val("str", jnp.empty(0, jnp.int32), np.empty(0, "<U1"))
        col = Column.from_values(np.asarray(out, dtype=object))
        return _Val(
            "str",
            jnp.asarray(col.data),
            col.dictionary,
            valid=None if col.validity is None else jnp.asarray(col.validity),
        )
    npdtype = np.dtype(expr.dtype)
    null_mask = np.fromiter((v is None for v in out), bool, count=n)
    fill = np.zeros((), npdtype).item()
    filled = np.asarray([fill if v is None else v for v in out], dtype=npdtype)
    valid = None if not null_mask.any() else jnp.asarray(~null_mask)
    return _Val("num", jnp.asarray(filled), valid=valid)


def _compare(op: str, a, b):
    if op == "==":
        return a == b
    if op == "!=":
        return a != b
    if op == "<":
        return a < b
    if op == "<=":
        return a <= b
    if op == ">":
        return a > b
    if op == ">=":
        return a >= b
    raise HyperspaceException(op)


def evaluate_column(expr: Expr, table: Table) -> Column:
    """Evaluate an arbitrary expression to a materialized Column (the withColumn
    executor). Invalid slots are re-filled with the canonical zero so downstream
    hashing/grouping over computed columns keeps the nulls-cluster invariant."""
    n = table.num_rows
    out = _compiled_eval(expr, table, "value")
    if out is not None:
        arr = np.asarray(out["arr"])
        valid = None if "valid" not in out else np.asarray(out["valid"], dtype=bool)
        if valid is not None and not valid.all():
            arr = np.where(valid, arr, np.zeros((), dtype=arr.dtype))
        from .schema import dtype_from_numpy

        return Column(dtype_from_numpy(arr.dtype), arr, None, valid)
    v = evaluate(expr, table, {})
    if v.kind == "str":
        codes = np.asarray(v.arr, dtype=np.int32)
        valid = None if v.valid is None else np.asarray(v.valid, dtype=bool)
        if valid is not None:
            codes = np.where(valid, codes, 0).astype(np.int32)
        return Column("string", codes, np.asarray(v.dictionary), valid)
    if v.kind == "lit":
        # (A bare None literal never reaches here: infer_expr_dtype rejects it
        # at plan construction.)
        if isinstance(v.value, str):
            return Column(
                "string", np.zeros(n, np.int32), np.asarray([v.value]), None
            )
        arr = np.full(n, v.value)
        if arr.dtype == np.bool_:
            pass
        elif np.issubdtype(arr.dtype, np.integer):
            arr = arr.astype(np.int64)
        else:
            arr = arr.astype(np.float64)
        return Column.from_values(arr)
    arr = np.asarray(v.arr)
    if arr.ndim == 0:
        # Literal arithmetic (e.g. lit(2) * lit(3)) evaluates to a 0-d array;
        # broadcast it to the table length like the bare-literal branch does.
        arr = np.full(n, arr[()], dtype=arr.dtype)
    valid = None if v.valid is None else np.asarray(v.valid, dtype=bool)
    if valid is not None and np.ndim(valid) == 0:
        valid = np.full(n, bool(valid))
    if valid is not None and not valid.all():
        arr = np.where(valid, arr, np.zeros((), dtype=arr.dtype))
    from .schema import dtype_from_numpy

    return Column(dtype_from_numpy(arr.dtype), arr, None, valid)


def _collect_col_spellings(expr: Expr) -> list:
    """Distinct column spellings as WRITTEN in the expression (evaluate() keys
    devcols by the expression's own spelling, so the compiled path must too)."""
    out = []

    def walk(e):
        if isinstance(e, Col):
            if e.name not in out:
                out.append(e.name)
        elif isinstance(e, BinaryOp):
            walk(e.left)
            walk(e.right)
        elif isinstance(e, (Not, IsNull, IsIn)):
            walk(e.child)
        elif isinstance(e, Udf):
            for a in e.args:
                walk(a)

    walk(expr)
    return sorted(out)


def _contains_udf(expr: Expr) -> bool:
    if isinstance(expr, Udf):
        return True
    return any(_contains_udf(c) for c in expr.children())


class _PredColMeta:
    """The column METADATA evaluate() reads at trace time — everything except
    the arrays themselves (those arrive as traced arguments)."""

    __slots__ = ("is_string", "dictionary", "validity")

    def __init__(self, is_string, dictionary, has_validity):
        self.is_string = is_string
        self.dictionary = dictionary
        self.validity = True if has_validity else None  # presence marker only


class _PredTableFacade:
    def __init__(self, num_rows: int, cols: dict):
        self.num_rows = num_rows
        self._cols = cols

    def column(self, name: str):
        return self._cols[name]


# Compiled predicates, LRU-capped. Key pins EVERYTHING the trace depends on:
# the expression (repr is structural + literal-valued), the row count, and per
# referenced spelling the dtype / stringness / dictionary identity / validity
# presence; dictionary liveness is re-verified by weakref on every hit.
from collections import OrderedDict as _OrderedDict
import threading as _threading

_PRED_CACHE: "_OrderedDict[tuple, tuple]" = _OrderedDict()
_PRED_CACHE_MAX = 256
_PRED_UNCACHEABLE: set = set()  # expr reprs whose trace failed (e.g. str-str compare)
_PRED_UNCACHEABLE_MAX = 1024  # bounded: a workload of one-off exprs must not grow it forever
_pred_lock = _threading.RLock()  # concurrent queries share the compiled-predicate memo


def _evaluate_predicate_eager(expr: Expr, table: Table) -> jnp.ndarray:
    v = evaluate(expr, table, {})
    if v.kind != "num" or v.arr.dtype != jnp.bool_:
        raise HyperspaceException(f"Not a boolean predicate: {expr!r}")
    if v.valid is None:
        return v.arr
    return jnp.logical_and(v.arr, v.valid)


def _build_compiled_fn(expr: Expr, facade: _PredTableFacade, spellings: list, mode: str):
    """mode="pred": boolean mask with unknowns dropped. mode="value": the raw
    numeric result as {"arr": ..., ["valid": ...]} (structure is deterministic
    per cache key, so callers can branch on membership)."""
    import jax

    def fn(*flat):
        devcols = {}
        i = 0
        for sp, has_valid in spellings:
            devcols[sp] = flat[i]
            i += 1
            if has_valid:
                devcols[f"__valid__{sp}"] = flat[i]
                i += 1
        v = evaluate(expr, facade, devcols)
        if mode == "pred":
            if v.kind != "num" or v.arr.dtype != jnp.bool_:
                raise HyperspaceException(f"Not a boolean predicate: {expr!r}")
            if v.valid is None:
                return v.arr
            return jnp.logical_and(v.arr, v.valid)
        if v.kind != "num" or v.arr.ndim == 0:
            # String/literal results (host packaging) stay on the eager path.
            raise HyperspaceException("uncompilable value expression")
        out = {"arr": v.arr}
        if v.valid is not None:
            out["valid"] = v.valid
        return out

    from ..telemetry.compile_log import observed_jit as _observed_jit

    return _observed_jit(fn, label="evaluate.compiled_expr")


#: Adaptive fusion guard for live/interactive workloads. The fused program's
#: compile cache keys on repr(expr) — the LITERAL VALUE included — and on the
#: table's exact row count, so an interactive point-lookup mix (rotating
#: literals, index generations flipping under live refresh) minted one ~15 ms
#: XLA compile per (literal, shape). Eager ops cache per SHAPE only (scalars
#: ride as weak-typed arguments) but cost one dispatch per operator, which a
#: warm streamed-aggregate loop over stable shapes measurably feels (~1 ms per
#: chunk at bench scale). So the policy is adaptive, per literal-abstracted
#: expression STRUCTURE, on CPU-backend tables below the size bound: FUSE by
#: default (stable workloads fuse once and stay fused, zero change), and once
#: one structure has minted `HYPERSPACE_PRED_FUSE_MAX_CLASSES` distinct fused
#: programs (= literals rotating or shapes churning — compiles, not reuse),
#: stop fusing it and evaluate eagerly over pow2-padded inputs instead. On the
#: device path every dispatch is a round-trip, so fusion always wins there.
#: MIN_ROWS=0 = always fuse (the pre-existing behavior, the fallback
#: contract); MAX_CLASSES=0 = never fuse below the size bound.
ENV_PRED_FUSE_MIN_ROWS = "HYPERSPACE_PRED_FUSE_MIN_ROWS"
_DEFAULT_PRED_FUSE_MIN_ROWS = 1 << 16
ENV_PRED_FUSE_MAX_CLASSES = "HYPERSPACE_PRED_FUSE_MAX_CLASSES"
_DEFAULT_PRED_FUSE_MAX_CLASSES = 3

_STRUCT_MINTS: Dict[str, int] = {}  # literal-abstracted structure → fused mints
_STRUCT_MINTS_MAX = 4096


def _env_int(key: str, default: int) -> int:
    import os

    try:
        v = os.environ.get(key, "")
        return int(v) if v != "" else default
    except ValueError:
        return default


def _pred_fuse_min_rows() -> int:
    return _env_int(ENV_PRED_FUSE_MIN_ROWS, _DEFAULT_PRED_FUSE_MIN_ROWS)


def _expr_structure(expr: Expr, mode: str) -> str:
    """Literal-abstracted identity of an expression (values → type names) —
    the same canonicalization plan fingerprints use, so `k == 7` and
    `k == 42` are ONE structure."""
    import json as _json

    from ..plananalysis.fingerprint import expr_signature

    return mode + ":" + _json.dumps(expr_signature(expr))


def _compiled_eval(expr: Expr, table: Table, mode: str):
    """Run `expr` over `table` as ONE compiled program per (mode, expression,
    table signature); None when this expression shape must stay eager (e.g.
    host access during trace: cross-column string compares, string/literal
    value results, or a small CPU-backend structure whose fused programs have
    stopped being reused — rotating literals / churning generations; see
    ENV_PRED_FUSE_MAX_CLASSES)."""
    import weakref

    if _contains_udf(expr):
        return None  # UDFs are host-evaluated by contract: never traced
    from ..ops.backend import use_device_path

    small_cpu = not use_device_path() and table.num_rows < _pred_fuse_min_rows()
    r = (mode, repr(expr))
    with _pred_lock:
        if r in _PRED_UNCACHEABLE:
            return None
    try:
        spellings = _collect_col_spellings(expr)
        sig = []
        metas = {}
        dict_refs = []
        for sp in spellings:
            col = table.column(sp)
            has_valid = col.validity is not None
            is_str = col.is_string
            sig.append(
                (
                    sp,
                    str(np.asarray(col.data).dtype),
                    is_str,
                    id(col.dictionary) if is_str else None,
                    has_valid,
                )
            )
            metas[sp] = _PredColMeta(is_str, col.dictionary, has_valid)
            if is_str:
                dict_refs.append((sp, weakref.ref(col.dictionary)))
        key = (r, table.num_rows, tuple(sig))
    except Exception:
        return None

    with _pred_lock:
        ent = _PRED_CACHE.get(key)
        if ent is not None:
            fn, refs, sp_flags = ent
            if all(wr() is table.column(sp).dictionary for sp, wr in refs):
                _PRED_CACHE.move_to_end(key)
            else:
                _PRED_CACHE.pop(key, None)
                ent = None
        if ent is None:
            if small_cpu:
                # Minting yet another fused program for this structure means
                # its literals/shapes are churning, not being reused: go
                # eager (pow2-padded for predicates) from here on.
                struct = _expr_structure(expr, mode)
                mints = _STRUCT_MINTS.get(struct, 0)
                if mints >= _env_int(
                    ENV_PRED_FUSE_MAX_CLASSES, _DEFAULT_PRED_FUSE_MAX_CLASSES
                ):
                    return None
                if len(_STRUCT_MINTS) >= _STRUCT_MINTS_MAX:
                    _STRUCT_MINTS.clear()  # bounded; counts are a heuristic
                _STRUCT_MINTS[struct] = mints + 1
            facade = _PredTableFacade(table.num_rows, metas)
            sp_flags = [(sp, metas[sp].validity is not None) for sp in spellings]
            fn = _build_compiled_fn(expr, facade, sp_flags, mode)
            _PRED_CACHE[key] = (fn, dict_refs, sp_flags)
            while len(_PRED_CACHE) > _PRED_CACHE_MAX:
                _PRED_CACHE.popitem(last=False)
        else:
            fn, _, sp_flags = ent

    from .device_cache import device_array

    flat = []
    for sp, has_valid in sp_flags:
        col = table.column(sp)
        flat.append(device_array(col.data))
        if has_valid:
            flat.append(device_array(col.validity))
    try:
        return fn(*flat)
    except Exception as e:
        # Fall back to the eager path for THIS call; permanently blacklist the
        # (mode, expression) shape only for trace-time failures (host access
        # during trace: TracerError/concretization). A transient device
        # error must not disable compilation for the shape forever.
        import jax

        trace_time = isinstance(
            e,
            (
                jax.errors.TracerArrayConversionError,
                jax.errors.ConcretizationTypeError,
                jax.errors.TracerBoolConversionError,
                HyperspaceException,
                TypeError,
            ),
        )
        with _pred_lock:
            _PRED_CACHE.pop(key, None)
            if trace_time:
                if len(_PRED_UNCACHEABLE) >= _PRED_UNCACHEABLE_MAX:
                    # Bounded: evict an arbitrary old entry rather than refuse
                    # the new one (a refused shape would re-trace and re-fail
                    # on every call — the exact cost the blacklist avoids).
                    _PRED_UNCACHEABLE.pop()
                _PRED_UNCACHEABLE.add(r)
        return None


def _pow2_padded_eager_mask(expr: Expr, table: Table):
    """CPU-backend eager predicate over POW2-PADDED column copies, sliced back
    to the true row count on the host. Eager ops compile per input SHAPE, so a
    live table whose row counts drift (every refresh/compaction generation,
    every hybrid-append merge) minted one ~20 ms XLA compile per new shape on
    the interactive path; padding onto the pow2 grid pins each (expression,
    dtype) pair to at most log2(N) compile classes — the PR-10 mesh compile
    contract applied to predicate evaluation. Padded slots carry zeros (and
    validity False where a mask exists); their mask bits are sliced off before
    anyone sees them. None = not applicable (already pow2, UDF, or a column
    that failed to resolve — the caller falls through to the plain path)."""
    n = table.num_rows
    if n == 0 or _contains_udf(expr):
        return None
    m = 1 << (n - 1).bit_length()
    if m == n:
        return None
    try:
        spellings = _collect_col_spellings(expr)
        cols = {}
        pad_payload = pad_padded = 0
        for sp in spellings:
            c = table.column(sp)
            data = np.asarray(c.data)
            pad_payload += n * int(data.dtype.itemsize)
            pad_padded += (m - n) * int(data.dtype.itemsize)
            data = np.concatenate([data, np.zeros(m - n, dtype=data.dtype)])
            valid = None
            if c.validity is not None:
                valid = np.concatenate([c.validity, np.zeros(m - n, dtype=bool)])
                pad_payload += n
                pad_padded += m - n
            cols[sp] = Column(c.dtype, data, c.dictionary, valid)
            if getattr(c, "_encoded_read", False):
                # Padded copies keep the encoded-read provenance so the
                # eager fallback's device staging still rides narrow codes.
                cols[sp]._encoded_read = True
        from ..telemetry import device_observatory as _devobs

        _devobs.record_pad("eval_mask", pad_payload, pad_padded)
    except Exception:
        return None
    mask = _evaluate_predicate_eager(expr, Table(cols))
    return np.asarray(mask)[:n]


def evaluate_predicate(expr: Expr, table: Table) -> jnp.ndarray:
    """Evaluate a boolean expression over a table → device mask. A row survives
    only when the predicate is TRUE and KNOWN (SQL WHERE drops unknowns).

    Device path: ONE compiled program per (expression, table signature) —
    eager evaluation issues one dispatch per operator, and on a remote PJRT
    transport each dispatch is a round-trip. CPU path below the fusion
    threshold: eager over pow2-padded inputs (shape-stable compile classes,
    literal values never in the compile key)."""
    out = _compiled_eval(expr, table, "pred")
    if out is not None:
        return out
    from ..ops.backend import use_device_path

    if not use_device_path() and table.num_rows < _pred_fuse_min_rows():
        # Size-gated like the fusion guard itself: a LARGE unfusable shape
        # (e.g. a cross-column string compare) must not pay a padded copy of
        # every referenced column per query.
        padded = _pow2_padded_eager_mask(expr, table)
        if padded is not None:
            return padded
    return _evaluate_predicate_eager(expr, table)
