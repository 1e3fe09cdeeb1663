"""Columnar expression evaluation as JAX array programs.

Counterpart of the reference's vectorized builtin evaluators (reference:
expression/builtin_*_vec.go over util/chunk columns), redesigned for XLA:
every expression lowers to pure jnp ops over (value, validity) array pairs,
so the whole scan->filter->project->aggregate pipeline fuses into one
compiled program — the role unistore's compiled "closure executor" plays
(reference: store/mockstore/unistore/cophandler/closure_exec.go), but on
the TPU's VPU/MXU instead of a Go interpreter.

Null semantics: SQL three-valued logic via Kleene AND/OR; comparisons and
arithmetic propagate NULL; predicates treat NULL as false at the filter.

String columns arrive as int32 dictionary codes; the compiler resolved all
string constants/predicates to codes or per-code lookup tables host-side
(see client.py), so only integer ops reach the device.
"""

from __future__ import annotations

from typing import Any, Callable

import jax.numpy as jnp
import numpy as np

from ..errno import CodedError
from ..plan.expr import Call, Col, Const, PlanExpr
from ..types.field_type import FieldType, TypeKind

# A column on device: (values, validity). validity True = not NULL.
VV = tuple[jnp.ndarray, jnp.ndarray]


class CompileError(Exception):
    """Raised when an expression can't lower to device ops (host fallback)."""


class DeviceError(CodedError):
    """The device compiler or runtime refused a program the planner's
    gates admitted (a Mosaic or XLA compile error, a runtime fault). It
    is the statement's error: answering from the host interpreter
    instead would be a slow correct result that hides that the chip did
    nothing. (HBM exhaustion on a fragment is the one counted degrade:
    copr/fragment.py device_refusal.)"""

    @classmethod
    def of(cls, exc: BaseException) -> "DeviceError":
        return cls(f"device program failed: {exc}")


def _np_dtype_for(ft: FieldType):
    """Device dtype: TPUs have no native 64-bit — integers/decimals/codes
    lower to int32 (exactness guaranteed by the planner's interval
    analysis + limb decomposition), floats to float32."""
    import numpy as np
    host = ft.np_dtype
    if host == np.dtype(np.int64):
        return np.dtype(np.int32)
    if host == np.dtype(np.float64):
        return np.dtype(np.float32)
    return host


def _scale_factor(diff: int) -> int:
    return 10 ** diff


def eval_expr(
    e: PlanExpr,
    columns: list[VV],
    prepared: dict[int, Any],
) -> VV:
    """Lower a resolved expression to jnp ops.

    columns: scan output columns as (value, valid) pairs.
    prepared: compiler-resolved payloads by id(expr-node) — string constants
    as codes, LIKE/IN code tables, etc. (built host-side in client.py).
    """
    if isinstance(e, Col):
        return columns[e.idx]
    if isinstance(e, Const):
        n = columns[0][0].shape[0] if columns else 1
        if e.value is None:
            return (jnp.zeros(n, dtype=_np_dtype_for(e.ftype)),
                    jnp.zeros(n, dtype=bool))
        v = prepared.get(id(e), e.value)
        arr = jnp.full(n, v, dtype=_np_dtype_for(e.ftype))
        return arr, jnp.ones(n, dtype=bool)
    assert isinstance(e, Call)
    return _eval_call(e, columns, prepared)


def _eval_call(e: Call, columns: list[VV], prepared: dict[int, Any]) -> VV:
    op = e.op

    def ev(x: PlanExpr) -> VV:
        return eval_expr(x, columns, prepared)

    # ---- logic (Kleene 3VL) ------------------------------------------------
    if op == "and":
        av, aval = _as_bool(ev(e.args[0]))
        bv, bval = _as_bool(ev(e.args[1]))
        value = av & bv
        known_false = (aval & ~av) | (bval & ~bv)
        valid = (aval & bval) | known_false
        return value & valid, valid
    if op == "or":
        av, aval = _as_bool(ev(e.args[0]))
        bv, bval = _as_bool(ev(e.args[1]))
        value = (av & aval) | (bv & bval)
        known_true = (aval & av) | (bval & bv)
        valid = (aval & bval) | known_true
        return value, valid
    if op == "not":
        av, aval = _as_bool(ev(e.args[0]))
        return (~av) & aval, aval
    if op == "isnull":
        _, aval = ev(e.args[0])
        return ~aval, jnp.ones_like(aval)

    # ---- comparisons -------------------------------------------------------
    if op in ("eq", "ne", "lt", "le", "gt", "ge"):
        a, b = e.args
        av, avl = ev(a)
        bv, bvl = ev(b)
        av, bv = _align_numeric(a, av, b, bv)
        fn: Callable = {
            "eq": jnp.equal, "ne": jnp.not_equal, "lt": jnp.less,
            "le": jnp.less_equal, "gt": jnp.greater, "ge": jnp.greater_equal,
        }[op]
        valid = avl & bvl
        return fn(av, bv) & valid, valid

    # ---- membership / pattern ---------------------------------------------
    if op == "in_values":
        av, avl = ev(e.args[0])
        values = prepared.get(id(e), e.extra)
        hit = jnp.zeros_like(avl)
        for v in values:
            hit = hit | (av == v)
        return hit & avl, avl
    if op == "like":
        # prepared: bool code-table over the dictionary
        av, avl = ev(e.args[0])
        table = prepared[id(e)]
        if isinstance(table, tuple):
            # (codes match?, the few codes): compares, no gather
            matching, codes = table
            hit = jnp.zeros(av.shape, bool)
            for c in codes:
                hit = hit | (av == c)
            return (hit if matching else ~hit) & avl, avl
        safe = jnp.clip(av, 0, table.shape[0] - 1)
        return table[safe] & avl, avl
    if op == "dict_lookup":
        # generic per-code lookup (string range predicates, collation compares)
        av, avl = ev(e.args[0])
        table = prepared[id(e)]
        safe = jnp.clip(av, 0, table.shape[0] - 1)
        return table[safe] & avl, avl

    # ---- arithmetic --------------------------------------------------------
    if op in ("add", "sub"):
        a, b = e.args
        av, avl = ev(a)
        bv, bvl = ev(b)
        av, bv = _align_decimal_args(a, av, b, bv, e.ftype)
        out = av + bv if op == "add" else av - bv
        return out, avl & bvl
    if op == "mul":
        a, b = e.args
        av, avl = ev(a)
        bv, bvl = ev(b)
        if e.ftype.is_float:
            av = _to_float(av)
            bv = _to_float(bv)
        # decimal mul: scales add up; no rescale needed
        return av * bv, avl & bvl
    if op == "div":
        a, b = e.args
        av, avl = ev(a)
        bv, bvl = ev(b)
        if not e.ftype.is_float:
            raise CompileError("decimal division is host-only")
        av = _to_float(av)
        bv = _to_float(bv)
        nonzero = bv != 0
        out = jnp.where(nonzero, av / jnp.where(nonzero, bv, 1.0), 0.0)
        return out, avl & bvl & nonzero  # MySQL: x/0 -> NULL
    if op == "intdiv":
        a, b = e.args
        av, avl = ev(a)
        bv, bvl = ev(b)
        nonzero = bv != 0
        safe_b = jnp.where(nonzero, bv, 1)
        q = jnp.abs(av) // jnp.abs(safe_b)
        q = jnp.where((av < 0) != (bv < 0), -q, q)  # trunc toward zero
        return q, avl & bvl & nonzero
    if op == "mod":
        a, b = e.args
        av, avl = ev(a)
        bv, bvl = ev(b)
        nonzero = bv != 0
        safe_b = jnp.where(nonzero, bv, 1)
        r = jnp.abs(av) % jnp.abs(safe_b)
        r = jnp.where(av < 0, -r, r)  # MySQL mod takes dividend sign
        return r, avl & bvl & nonzero
    if op == "neg":
        av, avl = ev(e.args[0])
        return -av, avl
    if op == "abs":
        av, avl = ev(e.args[0])
        return jnp.abs(av), avl

    # ---- control flow ------------------------------------------------------
    if op in ("if",):
        cv, cvl = _as_bool(ev(e.args[0]))
        tv, tvl = _cast_to(ev(e.args[1]), e.args[1].ftype, e.ftype)
        fv, fvl = _cast_to(ev(e.args[2]), e.args[2].ftype, e.ftype)
        cond = cv & cvl
        return jnp.where(cond, tv, fv), jnp.where(cond, tvl, fvl)
    if op == "ifnull":
        av, avl = _cast_to(ev(e.args[0]), e.args[0].ftype, e.ftype)
        bv, bvl = _cast_to(ev(e.args[1]), e.args[1].ftype, e.ftype)
        return jnp.where(avl, av, bv), avl | bvl
    if op == "coalesce":
        out_v, out_vl = _cast_to(ev(e.args[0]), e.args[0].ftype, e.ftype)
        for a in e.args[1:]:
            av, avl = _cast_to(ev(a), a.ftype, e.ftype)
            out_v = jnp.where(out_vl, out_v, av)
            out_vl = out_vl | avl
        return out_v, out_vl
    if op == "case":
        args = e.args
        has_else = len(args) % 2 == 1
        pairs = (len(args) - 1) // 2 if has_else else len(args) // 2
        if has_else:
            out_v, out_vl = _cast_to(ev(args[-1]), args[-1].ftype, e.ftype)
        else:
            n = columns[0][0].shape[0] if columns else 1
            out_v = jnp.zeros(n, dtype=_np_dtype_for(e.ftype))
            out_vl = jnp.zeros(n, dtype=bool)
        decided = jnp.zeros_like(out_vl)
        for i in range(pairs):
            cv, cvl = _as_bool(ev(args[2 * i]))
            tv, tvl = _cast_to(ev(args[2 * i + 1]), args[2 * i + 1].ftype,
                               e.ftype)
            take = (cv & cvl) & ~decided
            out_v = jnp.where(take, tv, out_v)
            out_vl = jnp.where(take, tvl, out_vl)
            decided = decided | take
        return out_v, out_vl

    # ---- temporal ----------------------------------------------------------
    if op in ("year", "month", "day"):
        av, avl = ev(e.args[0])
        if e.args[0].ftype.kind in (TypeKind.DATETIME, TypeKind.TIMESTAMP):
            av = av // 86_400_000_000  # micros -> days
        y, m, d = _civil_from_days(av)
        out = {"year": y, "month": m, "day": d}[op]
        return out.astype(jnp.int32), avl
    if op == "date_add_days":
        av, avl = ev(e.args[0])
        return av + int(e.extra), avl

    # ---- limb splits (wide-aggregate term decomposition, bounds.py) --------
    if op == "shr15":
        av, avl = ev(e.args[0])
        return av >> 15, avl
    if op == "and15":
        av, avl = ev(e.args[0])
        return av & 0x7FFF, avl

    # ---- casts -------------------------------------------------------------
    if op == "cast":
        src = e.args[0]
        return _cast_to(ev(src), src.ftype, e.ftype)

    raise CompileError(f"no device lowering for op {op!r}")


# ---- helpers ----------------------------------------------------------------

def _as_bool(vv: VV) -> VV:
    v, vl = vv
    if v.dtype != jnp.bool_:
        v = v != 0
    return v, vl


def _to_float(v: jnp.ndarray) -> jnp.ndarray:
    if not jnp.issubdtype(v.dtype, jnp.floating):
        return v.astype(jnp.float32)
    return v


def _align_numeric(a: PlanExpr, av, b: PlanExpr, bv):
    """Align operands for comparison: decimal scales, float promotion."""
    at, bt = a.ftype, b.ftype
    if at.is_float or bt.is_float:
        fa = _to_float(av)
        fb = _to_float(bv)
        if at.is_decimal:
            fa = fa / _scale_factor(at.scale)
        if bt.is_decimal:
            fb = fb / _scale_factor(bt.scale)
        return fa, fb
    sa = at.scale if at.is_decimal else 0
    sb = bt.scale if bt.is_decimal else 0
    if sa < sb:
        av = av * _scale_factor(sb - sa)
    elif sb < sa:
        bv = bv * _scale_factor(sa - sb)
    return av, bv


def _align_decimal_args(a: PlanExpr, av, b: PlanExpr, bv, out_t: FieldType):
    """Align for add/sub where the result type dictates the common scale."""
    if out_t.is_float:
        fa, fb = _align_numeric(a, av, b, bv)
        return fa, fb
    if out_t.is_decimal:
        sa = a.ftype.scale if a.ftype.is_decimal else 0
        sb = b.ftype.scale if b.ftype.is_decimal else 0
        s = out_t.scale
        if sa < s:
            av = av * _scale_factor(s - sa)
        if sb < s:
            bv = bv * _scale_factor(s - sb)
        return av, bv
    return av, bv


def _cast_to(vv: VV, src: FieldType, dst: FieldType) -> VV:
    v, vl = vv
    if src.kind == dst.kind and src.scale == dst.scale:
        return v, vl
    if dst.is_float:
        f = _to_float(v)
        if src.is_decimal:
            f = f / _scale_factor(src.scale)
        return f, vl
    if dst.is_decimal:
        if src.is_decimal:
            if src.scale < dst.scale:
                return v * _scale_factor(dst.scale - src.scale), vl
            if src.scale > dst.scale:
                # rescale with half-away rounding
                f = _scale_factor(src.scale - dst.scale)
                q = jnp.abs(v) + f // 2
                q = q // f
                return jnp.where(v < 0, -q, q), vl
            return v, vl
        if src.is_integer:
            return v * _scale_factor(dst.scale), vl
        if src.is_float:
            scaled = v * _scale_factor(dst.scale)
            q = jnp.floor(jnp.abs(scaled) + 0.5)
            return jnp.where(scaled < 0, -q, q).astype(jnp.int32), vl
        raise CompileError(f"cast {src!r} -> {dst!r} not on device")
    if dst.is_integer:
        if src.is_decimal:
            f = _scale_factor(src.scale)
            q = jnp.abs(v) + f // 2
            q = q // f
            return jnp.where(v < 0, -q, q), vl
        if src.is_float:
            q = jnp.floor(jnp.abs(v) + 0.5)
            return jnp.where(v < 0, -q, q).astype(jnp.int32), vl
        if src.is_integer or src.kind == TypeKind.BOOLEAN:
            return v.astype(jnp.int32), vl
    raise CompileError(f"cast {src!r} -> {dst!r} not on device")


def _civil_from_days(z: jnp.ndarray):
    """days-since-epoch -> (year, month, day), branch-free integer math
    (Howard Hinnant's civil_from_days; public-domain algorithm)."""
    z = z.astype(jnp.int32) + 719_468
    era = jnp.where(z >= 0, z, z - 146_096) // 146_097
    doe = z - era * 146_097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    return y, m, d


def selection_mask(
    conditions: list[PlanExpr],
    columns: list[VV],
    prepared: dict[int, Any],
    base: jnp.ndarray,
) -> jnp.ndarray:
    """Conjunctive filter: NULL condition results are false (SQL WHERE)."""
    mask = base
    for c in conditions:
        v, vl = _as_bool(eval_expr(c, columns, prepared))
        mask = mask & v & vl
    return mask
