"""Physical operators: dataframe algebra over partitioned frames (paper §4).

Each logical operator picks a partitioning scheme per the paper's §4.2 table:

  MAP / SELECTION / RENAME      → embarrassingly parallel, any partitioning
  GROUPBY(n)                    → row-parallel partial aggregation (MXU
                                  segment_reduce) + small combine — the
                                  shuffle-free plan the paper motivates
  GROUPBY(1)                    → same with G = 1 (pure reduction)
  WINDOW                        → blocked scan with cross-block carry
                                  composition (order-exact, still parallel)
  TRANSPOSE                     → per-block kernel transpose + grid swap
  SORT / JOIN                   → shuffle-native (``core/shuffle.py``):
                                  grace-hash join buckets / sample-sort range
                                  buckets exchanged through the pool, local
                                  per-bucket kernels, chunked payload gather —
                                  the inputs are never concatenated.
                                  ``REPRO_SHUFFLE=0`` retains the serial
                                  whole-frame path below as the oracle.
  DIFFERENCE / DROP-DUPLICATES  → blocking, but block-parallel: per-block key
                                  extraction through the scheduling layer,
                                  one host-side joint factorization, then
                                  blockwise keep-mask filters — the input is
                                  never concatenated (no ``to_frame()``).

Fused pipelines (paper §5 "Pipelining")
---------------------------------------
``FUSED_PIPELINE`` executes a whole chain of row-local operators (elementwise
MAP, SELECTION, PROJECTION, RENAME) as **one** per-row-partition program on
the shared pool: a single sweep over each block with column values staying on
device between stages, no intermediate ``PartitionedFrame``s, and one pool
dispatch for the whole chain instead of one per operator.  Runs of
consecutive structured-``Expr`` selections additionally collapse into a
single jit-compiled mask program (one XLA executable per predicate chain,
cached across blocks), so a k-predicate chain costs one device dispatch and
one filter instead of k of each.  Runs of consecutive elementwise MAPs are
likewise jit-traced as one XLA program per (udf-chain, schema), with a
per-chain fallback to eager dispatch when tracing fails or diverges.

Barrier-fused operators (fusion THROUGH the blocking boundary)
--------------------------------------------------------------
``FUSED_GROUPBY`` runs the row-local producer chain inside the groupby's own
per-block programs: one dispatch per partition stages the sweep and extracts
key spans, and (for dense INT keys) one dispatch per partition computes codes
plus every ``segment_reduce`` partial as a single compiled program — no
materialization boundary between the chain and the pre-shuffle stage.
``FUSED_SORT`` / ``FUSED_JOIN`` run the row-local consumer chain against the
permutation / match *index*: leading structured selections filter the index
before the payload gather and a leading projection prunes the gathered
columns, so the materialized frame is built once, post-filter, instead of
gathered-then-filtered.  ``FUSED_WINDOW`` folds pre-stages into the local-scan
block program and post-stages into the carry-application block program, with
the carry combine between them exactly where the unfused path placed it.
``FUSED_DROP_DUPLICATES`` / ``FUSED_DIFFERENCE`` run the row-local producer
chain inside the same per-block program that extracts the equality keys, and
consumer selections/projections filter the *keep mask* before the survivors
are materialized (the index-first pattern of ``FUSED_SORT``/``FUSED_JOIN``,
attributed via ``ExecStats.gather_rows``).

``REPRO_BLOCK_DEDUP=0`` routes DIFFERENCE / DROP-DUPLICATES through the
serial whole-frame path (the pre-PR-4 behavior) — the benchmark baseline and
an equivalence oracle for the block-parallel path.
"""
from __future__ import annotations

import functools
import logging
import math
import os
import threading
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import algebra as alg
from .dtypes import Domain, common_storage, parse_column, storage_dtype
from .frame import Column, Frame
from .labels import CodedLabels, IntLabels, Labels, RangeLabels, labels_from_values
from .partition import PartitionedFrame
from .schedule import (GRID_PREFS, count, dispatch_blocks, output_row_parts,
                       preferred_row_parts)
from .store import as_handle, pinned, resolve
from .trace import phase
from .transfer import note_h2d, to_device, to_host
from ..kernels import ops as kops

__all__ = ["run_node", "eval_expr", "NULL_CODE", "map_jit_counts"]

NULL_CODE = -1
_log = logging.getLogger(__name__)


# =============================================================================
# Expression evaluation (structured predicates / scalar exprs)
# =============================================================================
def _col_values(frame: Frame, name: Any) -> tuple[jnp.ndarray, jnp.ndarray, Column]:
    c = frame.col(name)
    return c.data, c.valid_mask(), c


def _eval_expr_core(expr: alg.Expr, getcol: Callable, nrows: int,
                    bin_hook: Callable | None = None,
                    full: Callable = jnp.full) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The one expression interpreter, shared by the interpreted per-frame
    path (``eval_expr``) and the jit-traced fused-predicate path
    (``_eval_expr_env``) so the two can never diverge.

    ``getcol(name) → (values, mask)``; ``bin_hook(BinExpr) → result | None``
    lets the frame path intercept coded-column comparisons (host code-table
    translation that cannot run under jit).  ``full`` builds literal arrays —
    the host path passes ``np.full`` so wide int64 columns compare in int64
    (a jax literal would promote the pair through int32 and truncate)."""
    if isinstance(expr, alg.ColRef):
        return getcol(expr.name)
    if isinstance(expr, alg.Lit):
        return full((nrows,), expr.value), jnp.ones((nrows,), jnp.bool_)
    if isinstance(expr, alg.UnaryExpr):
        v, mask = _eval_expr_core(expr.operand, getcol, nrows, bin_hook, full)
        if expr.op == "~":
            return ~v.astype(jnp.bool_), mask
        if expr.op == "isna":
            return ~mask, jnp.ones_like(mask)
        if expr.op == "notna":
            return mask, jnp.ones_like(mask)
        raise ValueError(expr.op)
    if isinstance(expr, alg.BinExpr):
        if bin_hook is not None:
            hit = bin_hook(expr)
            if hit is not None:
                return hit
        lv, lm = _eval_expr_core(expr.left, getcol, nrows, bin_hook, full)
        rv, rm = _eval_expr_core(expr.right, getcol, nrows, bin_hook, full)
        return _bin_numeric(expr.op, lv, lm, rv, rm)
    raise TypeError(expr)


def _host_full(shape, value):
    """Host literal arrays for the interpreted path, typed to match the
    jit-compiled fused path wherever both can run: in-range int literals in
    int32 (identical wrap semantics), float literals in float32 (identical
    arithmetic).  Only out-of-int32-range literals take int64 — they cannot
    be traced at all, and against a wide int64 host column the int⊕int
    promotion then compares exactly where a jax literal would truncate."""
    if not isinstance(value, bool) and isinstance(value, int):
        dt = np.int32 if -2 ** 31 <= value < 2 ** 31 else np.int64
        return np.full(shape, value, dtype=dt)
    if isinstance(value, float):
        return np.full(shape, value, dtype=np.float32)
    return np.full(shape, value)


def _has_wide_lit(expr: alg.Expr) -> bool:
    """True if any int literal in ``expr`` falls outside int32 — such a
    literal cannot be jit-traced (jax is 32-bit here), so predicate chains
    containing one run on the interpreted host path."""
    if isinstance(expr, alg.Lit):
        v = expr.value
        return (isinstance(v, int) and not isinstance(v, bool)
                and not -2 ** 31 <= v < 2 ** 31)
    if isinstance(expr, alg.BinExpr):
        return _has_wide_lit(expr.left) or _has_wide_lit(expr.right)
    if isinstance(expr, alg.UnaryExpr):
        return _has_wide_lit(expr.operand)
    return False


def eval_expr(expr: alg.Expr, frame: Frame) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Vectorized evaluation → (values, valid_mask) device arrays.  Inside a
    traced map run literals broadcast on the device, as in the compiled
    predicates, rather than entering the program as row-long constants."""
    def getcol(name):
        data, mask, _ = _col_values(frame, name)
        return data, mask

    def bin_hook(e: alg.BinExpr):
        # coded-column vs literal comparisons translate to code-space
        if isinstance(e.left, alg.ColRef) and isinstance(e.right, alg.Lit):
            c = frame.col(e.left.name)
            if c.domain.is_coded and e.op in ("==", "!="):
                code = _lit_to_code(c, e.right.value)
                v = c.data == code if e.op == "==" else c.data != code
                return v, c.valid_mask()
        return None

    traced = any(isinstance(c.data, jax.core.Tracer) for c in frame.columns)
    return _eval_expr_core(expr, getcol, frame.nrows, bin_hook,
                           jnp.full if traced else _host_full)


def null_free(expr: alg.Expr, frame: Frame) -> bool:
    """Whether ``expr`` over ``frame`` is valid in every row, known without
    reading a value (as inside a trace): its columns hold no mask, and no
    ``%`` or ``//`` can meet a zero divisor."""
    if isinstance(expr, alg.ColRef):
        return frame.col(expr.name).mask is None
    if isinstance(expr, alg.Lit):
        return True
    if isinstance(expr, alg.UnaryExpr):
        return expr.op in ("isna", "notna") or null_free(expr.operand, frame)
    if isinstance(expr, alg.BinExpr):
        return (expr.op not in ("%", "//") and null_free(expr.left, frame)
                and null_free(expr.right, frame))
    return False


def _lit_to_code(column: Column, value: Any) -> int:
    table = column.dictionary or ()
    key = str(value)
    return table.index(key) if key in table else -2  # -2 never matches


def _wide_host_int(a) -> bool:
    """True for a 64-bit integer HOST array — the one operand kind that must
    never meet jax arithmetic (canonicalization truncates int64 → int32)."""
    return (isinstance(a, np.ndarray) and a.dtype.kind in "iu"
            and a.dtype.itemsize > 4)


def _bin_numeric(op: str, lv, lm, rv, rm) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Binary op over (values, mask) pairs.  int⊕int stays in integer dtypes
    for ``+ - * % //`` and comparisons — a float32 round-trip corrupts values
    above 2²⁴ (int32 storage holds up to 2³¹−1).  Like numpy/pandas integer
    dtypes, ``+ - *`` wrap on int32 overflow; ``% //`` by zero yield null.
    A wide int64 host operand pins the pair to host numpy (a mixed np/jax op
    would canonicalize the wide side through int32 and truncate)."""
    _note_mixed(lm, rm)
    mask = lm & rm
    if op in ("&", "|"):
        _note_mixed(lv, rv)
        lb, rb = lv.astype(jnp.bool_), rv.astype(jnp.bool_)
        return (lb & rb if op == "&" else lb | rb), mask
    both_int = (jnp.issubdtype(lv.dtype, jnp.integer)
                and jnp.issubdtype(rv.dtype, jnp.integer))
    if both_int and (_wide_host_int(lv) or _wide_host_int(rv)):
        lv = to_host(lv, np.int64)
        rv = to_host(rv, np.int64)
    if op in ("+", "-", "*", "%", "//") and both_int:
        _note_mixed(lv, rv)
        if op == "+":
            return lv + rv, mask
        if op == "-":
            return lv - rv, mask
        if op == "*":
            return lv * rv, mask
        # int division by 0 is XLA-defined garbage (unlike float inf/nan):
        # mark those rows null instead of surfacing a plausible integer.  On
        # the host-numpy substrate a zero divisor would also warn, so feed
        # the masked slots a dummy 1 (their values are never observed).
        mask = mask & (rv != 0)
        if isinstance(lv, np.ndarray) and isinstance(rv, np.ndarray):
            rv = np.where(rv == 0, np.ones((), rv.dtype), rv)
            return (np.mod(lv, rv) if op == "%"
                    else np.floor_divide(lv, rv)), mask
        return (jnp.mod(lv, rv) if op == "%"
                else jnp.floor_divide(lv, rv)), mask
    if op in ("+", "-", "*", "/", "%", "//"):
        lf, rf = _as_float_pair(lv, rv)
        _note_mixed(lf, rf)
        if op in ("%", "//"):
            if isinstance(lf, np.ndarray) and lf.dtype.itemsize > 4:
                # the wide/f64 pair stays on host numpy end to end (jax mod
                # would truncate it back through f32); numpy warns where XLA
                # silently produces nan, so mute — the nan itself is kept
                with np.errstate(all="ignore"):
                    out = np.mod(lf, rf) if op == "%" else np.floor_divide(lf, rf)
            else:
                out = jnp.mod(lf, rf) if op == "%" else jnp.floor_divide(lf, rf)
        else:
            out = {"+": lf + rf, "-": lf - rf,
                   "*": lf * rf, "/": lf / rf}[op]
        return out, mask
    if both_int:
        lf, rf = lv, rv
    else:
        lf, rf = _as_float_pair(lv, rv)
    _note_mixed(lf, rf)
    out = {
        "==": lf == rf, "!=": lf != rf, "<": lf < rf,
        "<=": lf <= rf, ">": lf > rf, ">=": lf >= rf,
    }[op]
    return out, mask


def _as_float_pair(lv, rv):
    """Float substrate for a mixed binary op: float32 (device semantics,
    matching the jit-compiled fused path) unless either operand carries
    64-bit storage — then float64 on HOST numpy, the promotion numpy/pandas
    apply to int64⊕float (jax would truncate both sides through 32 bits).
    64-bit operands never reach the jit trace (the fused predicate path
    guards them out), so fused and unfused plans still agree."""
    try:
        wide = lv.dtype.itemsize > 4 or rv.dtype.itemsize > 4
    except AttributeError:
        wide = False
    if wide:
        return to_host(lv, np.float64), to_host(rv, np.float64)
    return lv.astype(jnp.float32), rv.astype(jnp.float32)


def _note_mixed(a, b) -> None:
    """A host operand of an op with a device operand is copied to the
    device: count it (``h2d_bytes``)."""
    if isinstance(a, jax.Array) != isinstance(b, jax.Array):
        note_h2d(a, b)


def _predicate_mask(frame: Frame, predicate) -> np.ndarray:
    if isinstance(predicate, alg.Udf):
        out = predicate.fn({n: c for n, c in zip(frame.col_labels.to_list(), frame.columns)}, frame)
        return to_host(out, bool)
    v, mask = eval_expr(predicate, frame)
    _note_mixed(v, mask)
    return to_host(v.astype(jnp.bool_) & mask)  # null comparisons → False


# =============================================================================
# Per-operator physical implementations
# =============================================================================
def _selection(pf: PartitionedFrame, predicate) -> PartitionedFrame:
    if pf.col_parts == 1:
        return pf.map_blockwise(lambda f: f.filter_rows(_predicate_mask(f, predicate)))
    # predicate may span column blocks: evaluate per row-stripe, filter blocks
    def stripe(i: int) -> list[Frame]:
        full = pf.parts[i][0]
        for j in range(1, pf.col_parts):
            full = full.concat_cols(pf.parts[i][j])
        keep = _predicate_mask(full, predicate)
        return [blk.filter_rows(keep) for blk in pf.parts[i]]
    rows = dispatch_blocks(stripe, range(pf.row_parts))
    return PartitionedFrame(rows)


def _project_block(frame: Frame, cols: Sequence[Any]) -> Frame:
    return frame.take_cols(frame.col_labels.positions_of(cols))


def _projection(pf: PartitionedFrame, cols: Sequence[Any]) -> PartitionedFrame:
    f = pf.repartition(col_parts=1)
    return f.map_blockwise(lambda frame: _project_block(frame, cols))


def _union(left: PartitionedFrame, right: PartitionedFrame) -> PartitionedFrame:
    l = left.repartition(col_parts=1)
    r = right.repartition(col_parts=1)
    # handle-level stack: pure metadata, no block is faulted
    return PartitionedFrame(l.handles + r.handles)


def _output_pf(out: Frame | PartitionedFrame) -> PartitionedFrame:
    """Re-grid a blocking operator's output to the pool width
    (``schedule.output_row_parts``): SORT/JOIN/... build a fresh frame, and
    handing it downstream as a single block would serialize every later
    operator.  Small results keep the old single-partition layout.  A
    PartitionedFrame input (DIFFERENCE / DROP-DUPLICATES keep the partitioned
    form all the way through) re-grids via the zero-copy segment regroup
    instead of a concat + re-split."""
    if isinstance(out, PartitionedFrame):
        return out.repartition(row_parts=output_row_parts(out.nrows),
                               col_parts=1)
    return PartitionedFrame.from_frame(out,
                                       row_parts=output_row_parts(out.nrows))


_HASH_MASK = (1 << 52) - 1  # exactly-representable ints in float64
_WIDE_INT_LIMIT = 1 << 53   # |v| beyond this, float64 merges distinct int64s


def _fnv64(s: str) -> int:
    h = 0xCBF29CE484222325
    for ch in s.encode():
        h ^= ch
        h = (h * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h


def _hash_wide_ints(v: np.ndarray) -> np.ndarray:
    """splitmix64-style mix of int64 key values, masked into the float64-exact
    range: keys for integers float64 cannot represent (a plain cast collides
    2**53 with 2**53 + 1).  Like the coded-column value hash, equality is
    probabilistic with a ~2**-52 per-pair collision chance — distinct wide
    keys separate, at the same odds strings already accept."""
    z = v.astype(np.int64).view(np.uint64).copy()
    z += np.uint64(0x9E3779B97F4A7C15)
    z ^= z >> np.uint64(30)
    z *= np.uint64(0xBF58476D1CE4E5B9)
    z ^= z >> np.uint64(27)
    z *= np.uint64(0x94D049BB133111EB)
    z ^= z >> np.uint64(31)
    return (z & np.uint64(_HASH_MASK)).astype(np.float64)


def _wide_key_values(arr: np.ndarray) -> np.ndarray:
    """Key values for a column at a wide-flagged position.  Integer (and
    bool) storage hashes directly.  A float/other column sharing the position
    (the OTHER frame's column was the wide one) hashes only its *integral*
    in-int64-range values — ``5.0`` must still equal int ``5`` — while
    fractional and non-finite values keep their raw float64 form: hash
    outputs are integers, so a fractional value can never falsely equal one."""
    if arr.dtype.kind in "iub":
        return _hash_wide_ints(arr)
    f = np.asarray(arr, dtype=np.float64)
    intlike = np.isfinite(f) & (np.floor(f) == f) & (np.abs(f) < 2.0 ** 63)
    hashed = _hash_wide_ints(np.where(intlike, f, 0.0).astype(np.int64))
    return np.where(intlike, hashed, f)


def _wide_int_flags(frame: Frame, subset: Sequence[Any] | None) -> np.ndarray:
    """Per-key-column bool: INT column holding values outside ±2**53 (only
    possible with int64 host storage — int32 device storage can't reach it).
    Every frame participating in one joint factorization must agree on these
    flags, or a wide column would hash on one side and value-cast on the
    other; callers OR the flags across frames/blocks before ``_row_keys``."""
    cols = frame.columns if subset is None else [frame.col(n) for n in subset]
    out = np.zeros(len(cols), dtype=bool)
    for i, c in enumerate(cols):
        # dtype check BEFORE np.asarray: only host int64 storage can be wide,
        # and materializing int32 device columns here would pay a per-column
        # per-block device→host copy just to skip them
        if c.domain is not Domain.INT or c.data.dtype.itemsize <= 4:
            continue
        v = to_host(c.data)
        if c.mask is not None:
            v = v[to_host(c.mask)]
        if v.size and bool(((v > _WIDE_INT_LIMIT) | (v < -_WIDE_INT_LIMIT)).any()):
            out[i] = True
    return out


def _row_keys(frame: Frame, subset: Sequence[Any] | None,
              wide: np.ndarray | None = None) -> np.ndarray:
    """Normalized per-row key matrix (host) for equality (dedup / difference /
    join / groupby).  Coded (Σ*) columns map through a *value* hash so keys
    compare correctly across frames with different dictionaries; numerics are
    their float64 values; nulls are NaN (never equal a valid key).  ``wide``
    (from ``_wide_int_flags``, OR-ed across all frames being compared) routes
    int64 columns exceeding the float64-exact range through the hash path."""
    cols = frame.columns if subset is None else [frame.col(n) for n in subset]
    mats = []
    for i, c in enumerate(cols):
        if c.domain.is_coded:
            table = c.dictionary or ()
            lut = np.asarray([float(_fnv64(str(v)) & _HASH_MASK) for v in table]
                             or [0.0], dtype=np.float64)
            # integer cast: a 0-row coded column may carry float storage
            codes = to_host(c.data).astype(np.int64, copy=False)
            v = lut[np.clip(codes, 0, len(lut) - 1)]
            v = np.where(codes >= 0, v, np.nan)
        elif wide is not None and bool(wide[i]):
            v = _wide_key_values(to_host(c.data))
        else:
            v = to_host(c.data, np.float64)
        if c.mask is not None:
            v = np.where(to_host(c.mask), v, np.nan)
        mats.append(v)
    return np.stack(mats, axis=1) if mats else np.zeros((frame.nrows, 0))


def _sort_rank_keys(frame: Frame, subset: Sequence[Any]) -> list[np.ndarray]:
    """Per-column sort keys: lexicographic rank for coded columns, values for
    numerics (ordering, unlike equality, needs real value order)."""
    out = []
    for name in subset:
        c = frame.col(name)
        if c.domain.is_coded:
            table = list(c.dictionary or ())
            rank = np.empty(max(len(table), 1), dtype=np.float64)
            for r, idx in enumerate(sorted(range(len(table)), key=lambda i: str(table[i]))):
                rank[idx] = r
            codes = to_host(c.data).astype(np.int64, copy=False)
            v = rank[np.clip(codes, 0, len(table) - 1 if table else 0)]
            v = np.where(codes >= 0, v, np.nan)
        else:
            v = to_host(c.data, np.float64)
        if c.mask is not None:
            v = np.where(to_host(c.mask), v, np.nan)
        out.append(v)
    return out


def _keys_to_ids(*key_mats: np.ndarray) -> list[np.ndarray]:
    """Jointly factorize row-key matrices → dense ids (NaN-safe)."""
    all_rows = np.concatenate(key_mats, axis=0)
    # use bit-view so NaN == NaN for grouping purposes
    view = all_rows.view(np.int64).reshape(all_rows.shape)
    n, ncols = view.shape
    if ncols == 0:
        # no key columns: every row carries the same (empty) key
        inv = np.zeros(n, dtype=np.int64)
    elif ncols == 1:
        # single-key fast path: 1-D unique (axis=0 unique void-sorts, ~30×
        # slower — this is the groupby(n) hot path)
        _, inv = np.unique(view[:, 0], return_inverse=True)
    else:
        # multi-key: column-wise factorization — k cheap 1-D uniques instead
        # of one void-sorted row unique (~30× constant).  Exact, no hashing.
        # The per-column uniques go through the pool (numpy's sort drops the
        # GIL, so the columns genuinely factorize in parallel).
        def col_inv(j: int):
            with phase("keys:unique"):
                _, invj = np.unique(view[:, j], return_inverse=True)
            return (invj.astype(np.int64),
                    int(invj.max()) + 1 if invj.size else 1)

        # attribute=False: these tasks are key COLUMNS, not row blocks — they
        # must not skew the row-block scheduling counters
        per_col = dispatch_blocks(col_inv, range(ncols), attribute=False)
        invs = [p[0] for p in per_col]
        cards = [p[1] for p in per_col]
        space = 1
        for c in cards:
            space *= c
        if space < 2 ** 62:
            # mixed-radix combine in ONE pass + one final unique: the code
            # (…(inv0·c1 + inv1)·c2 + inv2…) is the lexicographic rank in
            # the per-column rank space, so equal rows get equal codes
            code = invs[0]
            for invj, c in zip(invs[1:], cards[1:]):
                code = code * np.int64(c) + invj
            _, inv = np.unique(code, return_inverse=True)
        else:
            # huge code space: re-densify after every combine — the pair
            # code (prefix id × stride + column id) then never overflows
            # int64 because both factors are < n ≤ 2**31-ish
            inv = invs[0]
            for invj, c in zip(invs[1:], cards[1:]):
                _, inv = np.unique(inv * np.int64(c) + invj,
                                   return_inverse=True)
                inv = inv.astype(np.int64)
    out, off = [], 0
    for m in key_mats:
        out.append(inv[off:off + m.shape[0]].astype(np.int64))
        off += m.shape[0]
    return out


# ---- DIFFERENCE / DROP-DUPLICATES -------------------------------------------
# Block-parallel local-dedup → joint-factorize → blockwise-filter (the
# local-pattern decomposition Perera et al. describe for distinct/set ops):
# per-block key extraction runs through ``schedule.dispatch_blocks``, the
# per-block key matrices are jointly factorized in one host pass, and the
# first-occurrence / anti-join keep masks are applied blockwise — the input
# keeps its partitioned form end to end (no ``to_frame()`` concat).  The
# three steps are the caller's phase spans ``dedup:keys`` (regrid and the
# key-extraction round), ``dedup:ids`` and ``dedup:keep``.


def _block_dedup_enabled() -> bool:
    """``REPRO_BLOCK_DEDUP=0`` falls back to the serial whole-frame path (the
    pre-PR-4 seed behavior) — benchmark baseline and equivalence oracle."""
    return os.environ.get("REPRO_BLOCK_DEDUP", "") != "0"


def _dedup_grid_blocks(pf: PartitionedFrame, grid: str | None,
                       pref_key: str) -> list:
    """Full-width row blocks coarsened to the recorded grid preference (key
    extraction wants blocks ≈ workers: fewer per-block fixed costs — LUT
    builds, key-matrix stacks — and fewer pieces in the joint factorization).
    Unlike GROUPBY partials or WINDOW seams, dedup results are invariant to
    the blocking (keys are per-row, the factorization is joint), so the
    regrid may precede the absorbed producer chain: fused and unfused plans
    stay bit-identical on ANY grid, which lets the producer sweep and the key
    extraction share one pool round."""
    pf1 = pf.repartition(col_parts=1)
    rp = preferred_row_parts(pf1.row_parts, grid or GRID_PREFS[pref_key],
                             total_bytes=pf1.nbytes())
    if rp != pf1.row_parts:
        pf1 = pf1.repartition(row_parts=rp)
    return pf1.row_handles()


def _key_block(args) -> tuple[Any, np.ndarray, np.ndarray, np.ndarray | None]:
    """The per-block key-extraction program, ONE dispatch per partition: run
    the absorbed producer chain, induce, flag wide ints, build the key
    matrix, and evaluate pushable consumer predicates (row-local ⇒ legal on
    the pre-filter block, exactly like ``_fused_sort`` evaluates them on the
    unsorted frame).  Runs on a pool worker: the input faults under a pin,
    and the (possibly staged) block returns as a store handle so it can
    spill again before the keep-mask pass comes back for it."""
    block, subset, stages, preds = args
    with pinned(block) as src:
        f = (_run_stages_block(src, stages) if stages else src).induce()
        flags = _wide_int_flags(f, subset)
        mat = _row_keys(f, subset, flags)
        keep = None
        if preds:
            keep = np.asarray(_fused_selection_mask(preds, f), dtype=bool)
        hout = block if f is src else as_handle(
            f, recompute=lambda: (_run_stages_block(resolve(block), stages)
                                  if stages else resolve(block)).induce())
    return hout, flags, mat, keep


def _joint_key_mats(results, subset):
    """OR the per-block wide-int flags and re-key the (rare) blocks whose
    local decision disagrees — every block in one joint factorization must
    hash-or-cast each column identically (see ``_wide_int_flags``)."""
    blocks = [r[0] for r in results]
    flags = [r[1] for r in results]
    mats = [r[2] for r in results]
    keeps = [r[3] for r in results]
    joint = np.zeros_like(flags[0])
    for fl in flags:
        joint = joint | fl
    if joint.any():
        # re-key through the pool: serially re-keying the disagreeing blocks
        # would undo the block parallelism exactly on the wide-int inputs
        # this reconciliation exists for
        redo = [i for i, fl in enumerate(flags)
                if not bool((fl == joint).all())]

        def rekey(i):
            with pinned(blocks[i]) as f:
                return _row_keys(f, subset, joint)

        fixed = dispatch_blocks(rekey, redo)
        for i, m in zip(redo, fixed):
            mats[i] = m
    return blocks, mats, keeps


def _apply_keep_blocks(blocks: Sequence, keeps: Sequence[np.ndarray],
                       proj) -> PartitionedFrame:
    """Blockwise keep-mask filter (+ gather-time projection): the survivors
    are materialized once, post-filter, in their original partitioned form.
    Blocks are store handles — spilled ones fault inside the worker."""
    def filt(args):
        h, keep = args

        def build(f):
            g = f.filter_rows(keep)
            if proj is not None:
                g = _project_block(g, proj)
            return g

        with pinned(h) as f:
            return as_handle(build(f), recompute=lambda: build(resolve(h)))

    out = dispatch_blocks(filt, list(zip(blocks, keeps)))
    return PartitionedFrame([[b] for b in out])


def _dedup_finish(pfo: PartitionedFrame, rest) -> PartitionedFrame:
    out = _output_pf(pfo)
    if rest:
        out = out.map_blockwise(lambda b: _run_stages_block(b, rest))
    return out


def _difference(left: PartitionedFrame, right: PartitionedFrame, stats=None,
                pre_l: Sequence[alg.Stage] = (),
                pre_r: Sequence[alg.Stage] = (),
                post: Sequence[alg.Stage] = (),
                grid: str | None = None) -> PartitionedFrame:
    """Ordered anti-join on all columns: left rows whose full-row key appears
    in the right input are dropped, survivors keep left order and labels.
    Block-parallel: both sides' key extraction runs in ONE pool round, the
    anti-join membership test is a host np.isin over dense ids, and the keep
    masks filter the left blocks in place."""
    if not _block_dedup_enabled():
        return _difference_serial(left, right, stats, pre_l, pre_r, post)
    preds, proj, rest = _split_consumer_stages(post)
    with phase("dedup:keys"):
        lblocks = _dedup_grid_blocks(left, grid, "difference")
        rblocks = _dedup_grid_blocks(right, grid, "difference")
        items = ([(b, None, pre_l, preds) for b in lblocks]
                 + [(b, None, pre_r, ()) for b in rblocks])
        results = dispatch_blocks(_key_block, items)
    with phase("dedup:ids"):
        frames, mats, pred_keeps = _joint_key_mats(results, None)
        nl = len(lblocks)
        if stats is not None:
            stats.dedup_blocks += len(frames)
            stats.dedup_key_rows += sum(int(m.shape[0]) for m in mats)
        ids = _keys_to_ids(*mats)
        lids, rids = ids[:nl], ids[nl:]
        rset = np.unique(np.concatenate(rids))
        keeps = []
        for lid, pk in zip(lids, pred_keeps[:nl]):
            k = ~np.isin(lid, rset)
            if pk is not None:
                k = k & pk
            keeps.append(k)
    if stats is not None:
        stats.gather_rows += int(sum(int(k.sum()) for k in keeps))
    with phase("dedup:keep"):
        kept = _apply_keep_blocks(frames[:nl], keeps, proj)
    return _dedup_finish(kept, rest)


def _drop_duplicates(pf: PartitionedFrame, subset, stats=None,
                     pre: Sequence[alg.Stage] = (),
                     post: Sequence[alg.Stage] = (),
                     grid: str | None = None) -> PartitionedFrame:
    """First-occurrence dedup over the (subset) equality keys, block-parallel
    (see the section comment above).  A frame with no key columns has nothing
    to compare, so every row survives — pandas semantics."""
    if not _block_dedup_enabled():
        return _drop_duplicates_serial(pf, subset, stats, pre, post)
    preds, proj, rest = _split_consumer_stages(post)
    with phase("dedup:keys"):
        blocks = _dedup_grid_blocks(pf, grid, "drop_duplicates")
        results = dispatch_blocks(_key_block,
                                  [(b, subset, pre, preds) for b in blocks])
    with phase("dedup:ids"):
        frames, mats, pred_keeps = _joint_key_mats(results, subset)
        total = sum(int(m.shape[0]) for m in mats)
        if stats is not None:
            stats.dedup_blocks += len(frames)
            stats.dedup_key_rows += total
        if mats[0].shape[1] == 0:
            keep_global = np.ones(total, dtype=bool)
        else:
            all_ids = np.concatenate(_keys_to_ids(*mats))
            _, first = np.unique(all_ids, return_index=True)
            keep_global = np.zeros(total, dtype=bool)
            keep_global[first] = True
        keeps, off = [], 0
        for m, pk in zip(mats, pred_keeps):
            k = keep_global[off:off + m.shape[0]]
            off += m.shape[0]
            if pk is not None:
                k = k & pk
            keeps.append(k)
    if stats is not None:
        stats.gather_rows += int(sum(int(k.sum()) for k in keeps))
    with phase("dedup:keep"):
        kept = _apply_keep_blocks(frames, keeps, proj)
    return _dedup_finish(kept, rest)


def _difference_serial(left: PartitionedFrame, right: PartitionedFrame,
                       stats=None, pre_l=(), pre_r=(), post=()) -> PartitionedFrame:
    """The seed path: whole-frame concat + single-threaded host numpy."""
    if pre_l:
        left = _run_fused(left, pre_l)
    if pre_r:
        right = _run_fused(right, pre_r)
    lf, rf = left.to_frame().induce(), right.to_frame().induce()
    flags = _wide_int_flags(lf, None) | _wide_int_flags(rf, None)
    lids, rids = _keys_to_ids(_row_keys(lf, None, flags),
                              _row_keys(rf, None, flags))
    keep = ~np.isin(lids, np.unique(rids))
    if stats is not None:
        stats.dedup_blocks += 2
        stats.dedup_key_rows += lf.nrows + rf.nrows
        stats.gather_rows += int(keep.sum())
    out = _output_pf(lf.filter_rows(keep))
    if post:
        out = out.map_blockwise(lambda b: _run_stages_block(b, post))
    return out


def _drop_duplicates_serial(pf: PartitionedFrame, subset, stats=None,
                            pre=(), post=()) -> PartitionedFrame:
    """The seed path: whole-frame concat + single-threaded host numpy."""
    if pre:
        pf = _run_fused(pf, pre)
    f = pf.to_frame().induce()
    mat = _row_keys(f, subset, _wide_int_flags(f, subset))
    if mat.shape[1] == 0:
        keep = np.ones(f.nrows, dtype=bool)
    else:
        ids = _keys_to_ids(mat)[0]
        _, first = np.unique(ids, return_index=True)
        keep = np.zeros(f.nrows, dtype=bool)
        keep[first] = True
    if stats is not None:
        stats.dedup_blocks += 1
        stats.dedup_key_rows += f.nrows
        stats.gather_rows += int(keep.sum())
    out = _output_pf(f.filter_rows(keep))
    if post:
        out = out.map_blockwise(lambda b: _run_stages_block(b, post))
    return out


# ---- JOIN -------------------------------------------------------------------
def _match_ids(lids: np.ndarray, rids: np.ndarray, how: str):
    """Vectorized equality matching over factorized key ids — the shared
    kernel behind both the serial ``_join_indices`` path and the per-bucket
    local joins in ``core/shuffle.py``.  Reproduces the historical dict-loop
    matcher's exact emission order: left-major, right order breaking ties,
    unmatched-left rows interleaved in place (left/outer), unmatched-right
    rows appended in right order (right/outer).  Returns (lidx, ridx, lvalid,
    rvalid)."""
    nl, nr = int(lids.shape[0]), int(rids.shape[0])
    order_r = np.argsort(rids, kind="stable")
    srids = rids[order_r]
    # probe with SORTED queries (cache-friendly binary search: ~5× cheaper
    # than random-order probes), then scatter the results back to left order
    order_l = np.argsort(lids, kind="stable")
    slids = lids[order_l]
    starts = np.empty(nl, dtype=np.int64)
    ends = np.empty(nl, dtype=np.int64)
    starts[order_l] = np.searchsorted(srids, slids, side="left")
    ends[order_l] = np.searchsorted(srids, slids, side="right")
    counts = (ends - starts).astype(np.int64)
    matched = counts > 0
    if how in ("left", "outer"):
        out_counts = np.where(matched, counts, 1)
    else:
        out_counts = counts
    total = int(out_counts.sum())
    lidx = np.repeat(np.arange(nl, dtype=np.int64), out_counts)
    offs = np.cumsum(out_counts) - out_counts
    within = np.arange(total, dtype=np.int64) - np.repeat(offs, out_counts)
    rvalid = np.repeat(matched, out_counts)
    pos = np.repeat(starts.astype(np.int64), out_counts) + within
    if nr:
        gather = order_r[np.minimum(pos, nr - 1)].astype(np.int64)
    else:
        gather = np.zeros(total, dtype=np.int64)
    ridx = np.where(rvalid, gather, 0)
    lvalid = np.ones(total, dtype=bool)
    if how in ("right", "outer"):
        rpos = np.nonzero(~np.isin(rids, lids))[0].astype(np.int64)
        lidx = np.concatenate([lidx, np.zeros(rpos.shape[0], dtype=np.int64)])
        ridx = np.concatenate([ridx, rpos])
        lvalid = np.concatenate([lvalid,
                                 np.zeros(rpos.shape[0], dtype=bool)])
        rvalid = np.concatenate([rvalid, np.ones(rpos.shape[0], dtype=bool)])
    return lidx, ridx, lvalid, rvalid


def _join_indices(lf: Frame, rf: Frame, params: dict):
    """Build the match indices: (lidx, ridx, lvalid, rvalid, drop_right).
    No payload row is gathered here — that happens in ``_assemble_join``, and
    the fused-consumer path filters these indices first."""
    how = params["how"]
    on = params["on"]
    left_on = params["left_on"] or on
    right_on = params["right_on"] or on

    if left_on is None:  # CROSS-PRODUCT: nested order, left outer (Table 1 †)
        ml, mr = lf.nrows, rf.nrows
        lidx = np.repeat(np.arange(ml), mr)
        ridx = np.tile(np.arange(mr), ml)
        return lidx, ridx, None, None, ()

    flags = _wide_int_flags(lf, left_on) | _wide_int_flags(rf, right_on)
    lids, rids = _keys_to_ids(_row_keys(lf, left_on, flags),
                              _row_keys(rf, right_on, flags))
    lidx, ridx, lvalid, rvalid = _match_ids(lids, rids, how)
    drop_right = tuple(right_on) if on is not None else ()
    return lidx, ridx, lvalid, rvalid, drop_right


def _join(left: PartitionedFrame, right: PartitionedFrame, params: dict,
          stats=None) -> PartitionedFrame:
    from . import shuffle as _shuffle
    if _shuffle.enabled():
        return _shuffle.shuffled_join(left, right, params, (), stats)
    return _join_serial(left, right, params, stats)


def _join_serial(left: PartitionedFrame, right: PartitionedFrame, params: dict,
                 stats=None) -> PartitionedFrame:
    """The whole-frame oracle path (``REPRO_SHUFFLE=0``)."""
    lf, rf = left.to_frame().induce(), right.to_frame().induce()
    lidx, ridx, lvalid, rvalid, drop_right = _join_indices(lf, rf, params)
    if stats is not None:
        stats.gather_rows += int(lidx.shape[0])
    out = _assemble_join(lf, rf, lidx, ridx, lvalid, rvalid, drop_right)
    return _output_pf(out)


def _gather_join_cols(lf: Frame, rf: Frame, lidx, ridx, lvalid, rvalid,
                      drop_right, names: Sequence[Any]) -> Frame:
    """Gather ONLY the named columns of the (virtual) join result — the
    predicate's working set, not the payload.  Left columns shadow right ones
    on name collision, matching ``_assemble_join``'s concat order."""
    lnames = set(lf.col_labels.to_list())
    rnames = {n for n in rf.col_labels.to_list() if n not in drop_right}
    cols, out_names = [], []
    for n in names:
        if n in lnames:
            c, side_valid = lf.col(n).take(lidx), lvalid
        elif n in rnames:
            c, side_valid = rf.col(n).take(ridx), rvalid
        else:
            raise KeyError(n)
        if side_valid is not None and not side_valid.all():
            vm = to_device(c.valid_mask()) & to_device(side_valid)
            c = Column(c.data, c.domain, vm, c.dictionary)
        cols.append(c)
        out_names.append(n)
    return Frame(cols, RangeLabels(int(lidx.shape[0])), labels_from_values(out_names))


def _fused_join(left: PartitionedFrame, right: PartitionedFrame, params: dict,
                stages: Sequence[alg.Stage], stats=None) -> PartitionedFrame:
    from . import shuffle as _shuffle
    if _shuffle.enabled():
        return _shuffle.shuffled_join(left, right, params, stages, stats)
    return _fused_join_serial(left, right, params, stages, stats)


def _fused_join_serial(left: PartitionedFrame, right: PartitionedFrame,
                       params: dict, stages: Sequence[alg.Stage],
                       stats=None) -> PartitionedFrame:
    """Consumer fusion into JOIN: leading structured selections run against a
    gather of only the predicate's columns and filter the (lidx, ridx) match
    indices; the payload gather then builds only the surviving rows (and only
    the projected columns)."""
    lf, rf = left.to_frame().induce(), right.to_frame().induce()
    lidx, ridx, lvalid, rvalid, drop_right = _join_indices(lf, rf, params)
    preds, proj, rest = _split_consumer_stages(stages)
    row_labels = None
    if preds and lidx.shape[0]:
        refs = sorted(frozenset().union(*[p.refs() for p in preds]), key=repr)
        mini = _gather_join_cols(lf, rf, lidx, ridx, lvalid, rvalid,
                                 drop_right, refs)
        keep = np.asarray(_fused_selection_mask(preds, mini), dtype=bool)
        # the unfused path filters AFTER the join resets its index: surviving
        # rows keep their position in the unfiltered join result as label
        row_labels = RangeLabels(int(lidx.shape[0])).take(np.nonzero(keep)[0])
        lidx, ridx = lidx[keep], ridx[keep]
        lvalid = lvalid[keep] if lvalid is not None else None
        rvalid = rvalid[keep] if rvalid is not None else None
    if stats is not None:
        stats.gather_rows += int(lidx.shape[0])
    keep_cols = frozenset(proj) if proj is not None else None
    out = _assemble_join(lf, rf, lidx, ridx, lvalid, rvalid, drop_right,
                         keep_cols=keep_cols, row_labels=row_labels)
    if proj is not None:
        out = out.take_cols(out.col_labels.positions_of(proj))
    pfo = _output_pf(out)
    if rest:
        pfo = pfo.map_blockwise(lambda b: _run_stages_block(b, rest))
    return pfo


def _assemble_join(lf: Frame, rf: Frame, lidx, ridx, lvalid, rvalid, drop_right,
                   keep_cols: frozenset | None = None, row_labels=None) -> Frame:
    lsrc = lf
    if keep_cols is not None:
        lsrc = lf.take_cols([j for j, n in enumerate(lf.col_labels.to_list())
                             if n in keep_cols])
    lpart = lsrc.take_rows(lidx)
    keep_r = [j for j, n in enumerate(rf.col_labels.to_list())
              if n not in drop_right and (keep_cols is None or n in keep_cols)]
    rpart = rf.take_cols(keep_r).take_rows(ridx)
    lpart = _mask_all(lpart, lvalid)
    rpart = _mask_all(rpart, rvalid)
    out = lpart.concat_cols(rpart)
    if row_labels is None:
        row_labels = RangeLabels(out.nrows)   # reset index
    return Frame(out.columns, row_labels, out.col_labels)


def _mask_all(frame: Frame, valid: np.ndarray | None) -> Frame:
    if valid is None or valid.all():
        return frame
    vmask = to_device(valid)
    cols = [Column(c.data, c.domain, c.valid_mask() & vmask, c.dictionary) for c in frame.columns]
    return Frame(cols, frame.row_labels, frame.col_labels, frame.row_domains)


# ---- GROUPBY ----------------------------------------------------------------
_COMBINE = {"sum": "sum", "count": "sum", "min": "min", "max": "max"}
# the most group slots a dense-code groupby allocates (G of its programs)
_DENSE_CAP = 65536


def _groupby(pf: PartitionedFrame, keys: Sequence[Any], aggs: Sequence[tuple]) -> PartitionedFrame:
    """Row-parallel partial aggregation + tree combine (paper §4.2 Fig. 6).

    groupby(1) is ``keys == ()``: all rows fall into segment 0 and the combine
    is a pure reduction (any partitioning scheme works — paper's point).

    The working grid adapts to the pool width at plan time (same preference
    the fusion pass records on ``FusedGroupBy`` — blocks ≈ workers), so a
    256-partition frame on a 4-worker pool computes ~8 partials, not 256.
    """
    with phase("groupby:regrid"):
        rp = preferred_row_parts(pf.row_parts, GRID_PREFS["groupby"],
                                 total_bytes=pf.nbytes())
        pf = pf.repartition(row_parts=rp, col_parts=1)
        row_blocks = [row[0].induce() for row in pf.parts]
    return _groupby_blocks(row_blocks, keys, aggs)


def _groupby_blocks(row_blocks: list, keys: Sequence[Any],
                    aggs: Sequence[tuple]) -> PartitionedFrame:
    # the key step needs a global view of every block's keys (dictionaries,
    # INT spans, or the general factorization), so this path materializes
    # all blocks (handles fault here); the fused single-INT path above it is
    # the memory-governed one
    with phase("groupby:resolve"):
        row_blocks = [resolve(b) for b in row_blocks]
    with phase("groupby:keys"):
        dense = _dense_keys(row_blocks, keys)
        if dense is None:
            codes_per_block, G, rep_sorted = _factorize_keys(row_blocks, keys)
    if dense is not None:
        return _groupby_with_codes(row_blocks, keys, aggs, dense=dense)
    if keys:
        count("groupby_factorized")
    return _groupby_with_codes(row_blocks, keys, aggs, G=G,
                               codes_per_block=codes_per_block,
                               rep_sorted=rep_sorted)


def _value_order(v) -> tuple:
    """Sort key of one group key value: numbers by value, anything else by
    type name then value.  Groups come out in the lexicographic order of
    their key tuples under it, whichever route coded them."""
    return ("num", v) if isinstance(v, (int, float, bool)) else (str(type(v)), v)


class _DenseKey(NamedTuple):
    """One groupby key mapped onto a dense rank range: ``values[r]`` is the
    key value of rank r, in group order.  An INT key ranks ``v - vmin``; a
    coded key reads a block's codes through ``luts[id(dictionary)]``, whose
    last entry (-1) also takes the null code."""
    values: Sequence
    domain: Domain
    vmin: int | None = None
    luts: dict | None = None

    def ranks(self, c: Column) -> np.ndarray:
        """int32 ranks of one block's key column, NULL_CODE where null."""
        if self.vmin is None:
            codes = to_host(c.data)   # a 0-row coded column may be float
            r = self.luts[id(c.dictionary)][codes.astype(np.int32, copy=False)]
        else:
            r = (to_host(c.data, np.int64) - self.vmin).astype(np.int32)
        if c.mask is not None:
            r = np.where(to_host(c.mask), r, NULL_CODE)
        return r


def _dense_keys(row_blocks: list[Frame], keys) -> list[_DenseKey] | None:
    """Each key's dense rank range, or None where the general factorization
    must run: a key that is neither INT nor coded, an INT key with no valid
    value, an INT range or a dictionary union over ``_DENSE_CAP``, or a
    product of the ranges over it."""
    if not keys or not row_blocks:
        return None
    out, space = [], 1
    for k in keys:
        try:
            cols = [b.col(k) for b in row_blocks]
        except KeyError:
            return None
        dom = cols[0].domain
        if any(c.domain is not dom for c in cols):
            return None
        if dom is Domain.INT:
            span = _int_span(cols)
            dk = (None if span is None else
                  _DenseKey(range(span[0], span[1] + 1), dom, vmin=span[0]))
        elif dom.is_coded:
            dk = _coded_key(cols, dom)
        else:
            return None
        if dk is None:
            return None
        space *= len(dk.values)
        if space > _DENSE_CAP:
            return None
        out.append(dk)
    return out


def _int_span(cols: list[Column]) -> tuple[int, int] | None:
    """(min, max) of the valid values of an INT key's blocks; None if none."""
    vmin, vmax = None, None
    for c in cols:
        v = to_host(c.data, np.int64)
        if c.mask is not None:
            v = v[to_host(c.mask)]
        if v.size == 0:
            continue
        lo, hi = int(v.min()), int(v.max())
        vmin = lo if vmin is None else min(vmin, lo)
        vmax = hi if vmax is None else max(vmax, hi)
    return None if vmin is None else (vmin, vmax)


def _coded_key(cols: list[Column], dom: Domain) -> _DenseKey | None:
    """Rank range of a coded key: the union of its blocks' dictionaries in
    value order, and one local-code → rank LUT per distinct dictionary.
    Values are equal where their strings are, as ``_row_keys`` hashes them;
    None where two differing values share a string or the union is over
    ``_DENSE_CAP``."""
    tables = {id(c.dictionary): c.dictionary or () for c in cols}
    union: dict[str, Any] = {}
    for table in tables.values():
        for v in table:
            prev = union.setdefault(str(v), v)
            if type(prev) is not type(v) or prev != v:
                return None
        if len(union) > _DENSE_CAP:
            return None
    if not union:
        return None
    values = sorted(union.values(), key=_value_order)
    rank = {str(v): r for r, v in enumerate(values)}
    luts = {i: np.asarray([rank[str(v)] for v in t] + [NULL_CODE], np.int32)
            for i, t in tables.items()}
    return _DenseKey(values, dom, luts=luts)


def _dense_codes(block: Frame, keys, dense: Sequence[_DenseKey]) -> np.ndarray:
    """A block's group codes: the mixed-radix slot ``(r0·c1 + r1)·c2 + …``
    of its keys' ranks, which is the key tuple's lexicographic place, and
    NULL_CODE where any key is null (pandas drops null keys)."""
    ranks = [dk.ranks(block.col(k)) for k, dk in zip(keys, dense)]
    code = ranks[0]
    for r, dk in zip(ranks[1:], dense[1:]):
        code = code * np.int32(len(dk.values)) + r
    if len(ranks) > 1:
        null = ranks[0] < 0
        for r in ranks[1:]:
            null |= r < 0
        code[null] = NULL_CODE
    return code


def _factorize_keys(row_blocks: list, keys: Sequence[Any]):
    """Global key factorization (one column set to host): per-block group
    codes in the lexicographic order of the groups' key values, the group
    count, and each group's representative key values."""
    if keys:
        flags = np.zeros(len(keys), dtype=bool)
        for b in row_blocks:
            flags |= _wide_int_flags(b, keys)
        key_mats = [_row_keys(b, keys, flags) for b in row_blocks]
        ids_per_block = _keys_to_ids(*key_mats)
        all_ids = np.concatenate(ids_per_block)
        all_keys = np.concatenate(key_mats, axis=0)
        valid_rows = ~np.isnan(all_keys).any(axis=1)  # pandas drops null keys
        valid_idx = np.nonzero(valid_rows)[0]
        uniq_ids, first = np.unique(all_ids[valid_rows], return_index=True)
        first_global = valid_idx[first]
        # decode representative key VALUES (O(G·K) single lookups) so output
        # groups sort lexicographically by value, not by hash/code
        offsets = np.cumsum([0] + [b.nrows for b in row_blocks])
        def decode_row(gidx: int) -> tuple:
            bi = int(np.searchsorted(offsets, gidx, side="right") - 1)
            local = int(gidx - offsets[bi])
            return tuple(row_blocks[bi].col(k).value_at(local) for k in keys)
        rep_vals = [decode_row(int(gi)) for gi in first_global]
        perm = sorted(range(len(rep_vals)), key=lambda i: tuple(
            _value_order(v) for v in rep_vals[i]))
        order = uniq_ids[np.asarray(perm, dtype=np.int64)] if len(perm) else uniq_ids
        rep_sorted = [rep_vals[i] for i in perm]
        G = len(order)
        n_ids = int(all_ids.max()) + 1 if all_ids.size else 0
        remap = np.full(n_ids, NULL_CODE, dtype=np.int32)
        remap[order] = np.arange(G, dtype=np.int32)
        codes_per_block = [remap[ids] if ids.size else ids.astype(np.int32)
                           for ids in ids_per_block]
    else:
        G = 1
        rep_sorted = None
        codes_per_block = [np.zeros(b.nrows, dtype=np.int32) for b in row_blocks]
    return codes_per_block, G, rep_sorted


def _agg_need(aggs) -> list[tuple[Any, str]]:
    """The (column, base-statistic) partial vectors an agg list requires."""
    need: list[tuple[Any, str]] = []
    for col_label, func, _ in aggs:
        for base in _bases_for(func):
            if (col_label, base) not in need:
                need.append((col_label, base))
    return need


_PRESENCE = ("__presence__", "sum")


def _block_partial(block: Frame, codes, G: int, need: Sequence[tuple],
                   presence: bool) -> dict:
    """Per-block partial aggregates as ONE compiled program
    (``kernels.ops.segment_reduce_multi``): null masking, squaring, presence
    (so empty dense-range slots drop after the combine), and one
    ``segment_reduce`` per reduce op with same-op columns batched (M, C)."""
    outs = kops.segment_reduce_multi(
        [block.col(col_label).data for col_label, _ in need],
        [block.col(col_label).mask for col_label, _ in need],
        codes, bases=[base for _, base in need], num_segments=G,
        presence=presence)
    result = {key: outs[i] for i, key in enumerate(need)}
    if presence:
        result[_PRESENCE] = outs[len(need)]
    return result


def _combine_partials(partials: Sequence[dict], want: Sequence[tuple]) -> dict:
    """Tree combine of per-block partials (G-sized, tiny vs data)."""
    combined: dict[tuple, jnp.ndarray] = {}
    for key in want:
        base = key[1]
        parts = [p[key] for p in partials]
        acc = parts[0]
        for nxt in parts[1:]:
            if base in ("sum", "count", "sumsq"):
                acc = acc + nxt
            elif base == "min":
                acc = jnp.minimum(acc, nxt)
            else:
                acc = jnp.maximum(acc, nxt)
        combined[key] = acc
    return combined


def _groupby_with_codes(blocks: list, keys, aggs, *, dense=None, G: int = 1,
                        codes_per_block=None, rep_sorted=None) -> PartitionedFrame:
    """Per-block partials (parallel; one ``segment_reduce_multi`` program
    each), tree combine, finalize.  With ``dense`` keys each block's codes
    are computed inside its own task, under ``groupby:keys``, over every
    slot of the rank space, and the slots no row filled drop after the
    combine; else the blocks' ``codes_per_block`` index ``G`` groups."""
    need = _agg_need(aggs)
    if dense is not None:
        count("groupby_dense")
        G = math.prod(len(dk.values) for dk in dense)

        def block_partial(block) -> dict:
            with pinned(block) as f:
                with phase("groupby:keys"):
                    codes = _dense_codes(f, keys, dense)
                return _block_partial(f, codes, G, need, presence=True)

        partials = dispatch_blocks(block_partial, blocks)
        need = need + [_PRESENCE]
    else:
        partials = dispatch_blocks(
            lambda bc: _block_partial(bc[0], bc[1], G, need, presence=False),
            list(zip(blocks, codes_per_block)))
    return _combine_finalize(partials, need, blocks[0] if blocks else None,
                             keys, aggs, G, rep_sorted, dense)


def _combine_finalize(partials, need, template, keys, aggs, G: int,
                      rep_sorted=None, dense=None) -> PartitionedFrame:
    with phase("groupby:combine"):
        combined = _combine_partials(partials, need)
    with phase("groupby:finalize"):
        return _finalize_groupby(combined, template, keys, aggs, G,
                                 rep_sorted, dense)


def _finalize_groupby(combined: dict, template: Frame | None, keys, aggs,
                      G: int, rep_sorted=None, dense=None) -> PartitionedFrame:
    out_cols: list[Column] = []
    out_names: list[Any] = []
    keep = None
    # key columns first (the groups' key values, in group order)
    if dense is not None:
        # the slots some row filled are the groups; a slot's mixed-radix
        # digits are its keys' ranks
        keep = np.nonzero(to_host(combined[_PRESENCE]) > 0)[0]
        digits = np.unravel_index(keep, [len(dk.values) for dk in dense])
        for kname, dk, ranks in zip(keys, dense, digits):
            out_cols.append(_host_column([dk.values[r] for r in ranks.tolist()],
                                         dk.domain))
            out_names.append(kname)
    elif keys:
        template = resolve(template)   # only this branch needs block data
        for kpos, kname in enumerate(keys):
            src = template.col(kname)
            vals = [r[kpos] for r in rep_sorted]
            dom = src.domain if src.domain is not Domain.UNSPECIFIED else None
            out_cols.append(_host_column(vals, dom))
            out_names.append(kname)
    for col_label, func, out_label in aggs:
        cnt = combined.get((col_label, "count"))
        if func == "count":
            vals = cnt
        elif func == "sum":
            vals = combined[(col_label, "sum")]
        elif func == "mean":
            vals = combined[(col_label, "sum")] / jnp.maximum(cnt, 1.0)
        elif func in ("min", "max"):
            vals = combined[(col_label, func)]
        elif func in ("var", "std"):
            s, ss = combined[(col_label, "sum")], combined[(col_label, "sumsq")]
            var = (ss - s * s / jnp.maximum(cnt, 1.0)) / jnp.maximum(cnt - 1.0, 1.0)
            vals = jnp.sqrt(jnp.maximum(var, 0.0)) if func == "std" else var
        elif func == "any":
            vals = (combined[(col_label, "max")] > 0).astype(jnp.float32)
        elif func == "all":
            vals = (combined[(col_label, "min")] > 0).astype(jnp.float32)
        else:
            raise ValueError(func)
        mask = cnt > 0 if cnt is not None else None
        dom = Domain.INT if func == "count" else (Domain.BOOL if func in ("any", "all") else Domain.FLOAT)
        data = vals.astype(storage_dtype(dom))
        col = Column(data, dom, mask if func != "count" else None, None)
        out_cols.append(col if keep is None else col.take(keep))
        out_names.append(out_label)

    nrows = G if keep is None else len(keep)
    return _output_pf(Frame(out_cols, RangeLabels(nrows),
                            labels_from_values(out_names)))


def _bases_for(func: str) -> tuple[str, ...]:
    return {
        "sum": ("sum", "count"), "count": ("count",), "mean": ("sum", "count"),
        "min": ("min", "count"), "max": ("max", "count"),
        "var": ("sum", "sumsq", "count"), "std": ("sum", "sumsq", "count"),
        "any": ("max", "count"), "all": ("min", "count"),
    }[func]


def _host_column(values: list, domain: Domain) -> Column:
    if domain is Domain.INT:
        ints = [int(v) for v in values if v is not None]
        if ints and not all(-2 ** 31 <= v < 2 ** 31 for v in ints):
            # decoded groupby keys beyond int32: exact int64 HOST storage
            # (parse_column would raise — int64 must never reach jnp.asarray,
            # which truncates without x64; this column is only inspected /
            # re-keyed on host)
            data = np.asarray([0 if v is None else int(v) for v in values],
                              dtype=np.int64)
            mask = np.asarray([v is not None for v in values])
            return Column(data, Domain.INT,
                          None if mask.all() else mask, None)
    p = parse_column(values, domain)
    return Column(p.data, p.domain, p.mask, p.dictionary)


# ---- FUSED GROUPBY: producer chain inside the partial-aggregation program ----
def _fused_groupby(pf: PartitionedFrame, stages: Sequence[alg.Stage],
                   keys: Sequence[Any], aggs: Sequence[tuple],
                   grid: str | None = None) -> PartitionedFrame:
    """Producer fusion into GROUPBY (Cylon-style local-pattern fusion into the
    shuffle stage): the row-local chain runs inside the groupby's own
    per-block programs instead of materializing between the two.

    Pass A (one dispatch per partition) runs the whole producer sweep and
    extracts the block's key span — cheap host stats, no aggregation yet, so
    nothing is computed speculatively.  The spans agree on ONE global dense
    range, and pass B (one dispatch per partition) computes codes against it
    plus all ``segment_reduce`` partials in a single compiled program
    (``kernels.ops.segment_reduce_multi``) — a global static G means one XLA
    executable shared by every block and every query on the same schema,
    where per-block local ranges would recompile per distinct span.  Other
    key sets (coded or multi-key, range > 65536) go to ``_groupby_blocks``
    over the staged blocks — the producer sweep still ran fused, in one pool
    round instead of one per operator.

    Where the chain allows it, ``_masked_groupby`` runs instead: one pool
    round over the source blocks, the selections a device code mask, and
    nothing staged."""
    pf1 = pf.repartition(col_parts=1)
    blocks = [row[0] for row in pf1.handles]
    out = _masked_groupby(blocks, stages, keys, aggs, grid)
    if out is not None:
        return out
    single_key = len(keys) == 1

    def stage_block(block):
        with pinned(block) as src:
            f = _run_stages_block(src, stages).induce()
            info = None
            if single_key:
                try:
                    c = f.col(keys[0])
                except KeyError:
                    c = None
                if c is not None and c.domain is Domain.INT:
                    with phase("groupby:keys"):
                        v = to_host(c.data, np.int64)
                        if c.mask is not None:
                            v = v[to_host(c.mask)]
                        info = ((int(v.min()), int(v.max())) if v.size
                                else "empty")
            # staged output back into the store: under a budget it can spill
            # before the partial pass returns for it
            hout = block if f is src else as_handle(
                f, recompute=lambda: _run_stages_block(
                    resolve(block), stages).induce())
        return hout, info

    results = dispatch_blocks(stage_block, blocks)
    staged = [r[0] for r in results]
    infos = [r[1] for r in results]

    # plan-time grid adaptation: regroup the STAGED blocks to the recorded
    # preference (blocks ≈ workers) before the partial pass.  Staging first
    # and regridding second is what keeps the fused plan bit-identical to its
    # unfused counterpart — the unfused GROUPBY receives exactly this staged
    # block sequence as its materialized input and makes the same regroup
    # decision, so both paths compute partials over the same row groupings.
    # (Key spans are global min/max — regrouping cannot change them.)
    with phase("groupby:regrid"):
        rp = preferred_row_parts(len(staged),
                                 grid or GRID_PREFS["fused_groupby"],
                                 total_bytes=sum(h.nbytes for h in staged))
        if rp != len(staged):
            staged = [row[0] for row in
                      PartitionedFrame([[b] for b in staged])
                      .repartition(row_parts=rp).handles]

    spans = [i for i in infos if isinstance(i, tuple)]
    if single_key and spans and all(i is not None for i in infos):
        gmin = min(i[0] for i in spans)
        G = max(i[1] for i in spans) - gmin + 1
        if G <= _DENSE_CAP:
            dense = [_DenseKey(range(gmin, gmin + G), Domain.INT, vmin=gmin)]
            return _groupby_with_codes(staged, keys, aggs, dense=dense)

    # every other key set over the staged blocks: dense codes where each key
    # has a small rank range, else the general factorization; either needs
    # a global view, but the whole producer sweep still ran as one pool round
    return _groupby_blocks(staged, keys, aggs)


# ---- masked producer fusion: the fused groupby's selections as a code mask --
def _masked_plan(stages: Sequence[alg.Stage], keys, aggs, first: Frame):
    """(source columns the stages and aggregates read, the keys' source
    names) when every stage can run over just those columns of an
    uncompacted block, in one walk over the chain from the schema of the
    ``first`` block: structured selections, elementwise maps that declare
    what they read and set (``Udf.deps`` / ``writes``) and read no coded
    column (its codes need the host code table), projections, and renames
    that leave every label distinct.  None for any other chain, for a key
    that is not an INT or coded source column, or where a column read is a
    64-bit host column (the device would truncate it)."""
    names = first.col_labels.to_list()
    dom = dict(zip(names, first.schema))
    if len(dom) != len(names):
        return None
    origin = {n: n for n in names}     # label now → its source column, or
    reads = set()                      # None for a map's output

    def read(cols) -> bool:
        if not set(cols) <= origin.keys():
            return False
        reads.update(origin[c] for c in cols if origin[c] is not None)
        return True

    for st in stages:
        if st.op == "selection":
            pred = st.params["predicate"]
            if not isinstance(pred, alg.Expr) or not read(pred.refs()):
                return None
        elif st.op == "map":
            u = st.params["udf"]
            if (not u.elementwise or u.deps is None or u.writes is None
                    or not read(u.deps)
                    or any(origin[d] is not None and dom[origin[d]].is_coded
                           for d in u.deps)):
                return None
            origin.update(dict.fromkeys(u.writes))
        elif st.op == "projection":
            cols = list(st.params["cols"])
            if not set(cols) <= origin.keys() or len(set(cols)) != len(cols):
                return None
            origin = {c: origin[c] for c in cols}
        elif st.op == "rename":
            m = dict(st.params["mapping"])
            renamed = {m.get(n, n): o for n, o in origin.items()}
            if len(renamed) != len(origin):
                return None
            origin = renamed
        else:
            return None
    if not read({c for c, _, _ in aggs}) or not set(keys) <= origin.keys():
        return None
    src_keys = [origin[k] for k in keys]
    if (any(k is None or not (dom[k] is Domain.INT or dom[k].is_coded)
            for k in src_keys)
            or any(isinstance(d := first.col(c).data, np.ndarray)
                   and d.dtype.itemsize > 4 for c in reads)):
        return None
    return reads, src_keys


def _device_column(c: Column) -> Column:
    """``c`` with its host data and mask sent to the device once."""
    mask = c.mask if c.mask is None else to_device(c.mask)
    return Column(to_device(c.data), c.domain, mask, c.dictionary)


def _masked_map(frame: Frame, udfs: Sequence[alg.Udf]) -> Frame:
    """A run of maps over just the columns it reads (``_run_map_stages``:
    one traced program where the chain traces), with the columns it sets
    put into ``frame``."""
    reads = frozenset().union(*(u.deps for u in udfs))
    writes = frozenset().union(*(u.writes for u in udfs))
    names = frame.col_labels.to_list()
    out = _run_map_stages(
        frame.take_cols([j for j, n in enumerate(names) if n in reads]), udfs)
    cols = dict(zip(names, frame.columns))
    cols.update((n, c) for n, c in zip(out.col_labels.to_list(), out.columns)
                if n in writes)
    return Frame(list(cols.values()), frame.row_labels,
                 labels_from_values(list(cols)))


def _masked_stages(block: Frame, stages: Sequence[alg.Stage], reads) -> tuple:
    """A fused groupby's stages over the block's ``reads`` columns on the
    device, no row removed: (the staged columns, the device keep-mask of
    the selections, None without one).  The AND of row-local predicates is
    exact whatever later maps compute in the rows it rejects."""
    names = block.col_labels.to_list()
    cur = block.take_cols([j for j, n in enumerate(names) if n in reads])
    cur = Frame([_device_column(c) for c in cur.columns], cur.row_labels,
                cur.col_labels)
    keep, i = None, 0
    while i < len(stages):
        op = stages[i].op
        j = i + 1
        while op in ("selection", "map") and j < len(stages) and stages[j].op == op:
            j += 1
        if op == "selection":
            k = _selection_keep([st.params["predicate"] for st in stages[i:j]], cur)
            keep = k if keep is None else keep & k
        elif op == "map":
            cur = _masked_map(cur, [st.params["udf"] for st in stages[i:j]])
        elif op == "rename":
            cur = _rename_block(cur, dict(stages[i].params["mapping"]))
        else:                     # a projection, of the columns ``cur`` holds
            pos = {n: k for k, n in enumerate(cur.col_labels.to_list())}
            cur = cur.take_cols([pos[c] for c in stages[i].params["cols"]
                                 if c in pos])
        i = j
    return cur, keep


@jax.jit
def _kept_first(keep, codes, datas, masks):
    """The kept rows first, in their order, and NULL_CODE after them.  The
    partial program sums each row tile as one matmul, so a kept row must sit
    where a compacted block would hold it for the sums to round as the
    unfused plan's do; the shape stays the block's.

    Each kept row moves left by the number of rejected rows before it, one
    bit of that distance per step, lowest bit first: a static shift and a
    select per step, where a row gather or a sort costs many times more on a
    TPU.  The distance never falls from one kept row to the next, so two
    rows never land on one slot."""
    n = keep.shape[0]
    dist = jnp.where(keep, jnp.cumsum(~keep, dtype=jnp.int32), 0)
    vals = [codes, dist, *datas, *(m for m in masks if m is not None)]
    held = keep
    step = 1
    while step < n:
        move = held & ((dist & step) != 0)
        stay = held & ~move
        held = _shift_left(move, step) | stay
        vals = [jnp.where(held, jnp.where(stay, v, _shift_left(v, step)),
                          jnp.zeros((), v.dtype)) for v in vals]
        dist = vals[1]
        step *= 2
    codes, _, *rest = vals
    it = iter(rest[len(datas):])
    return (jnp.where(held, codes, NULL_CODE), rest[:len(datas)],
            [None if m is None else next(it) for m in masks])


def _shift_left(v, step: int):
    """``v`` moved ``step`` rows toward the front, zero-filled at the end."""
    return jnp.concatenate([v[step:], jnp.zeros((step,), v.dtype)])


def _masked_partial_input(frame: Frame, keep, codes, nrows: int) -> tuple:
    """(columns, group codes) for the partial program, on the device: every
    row the selections rejected takes NULL_CODE, so it reaches no segment
    and no presence slot; ``codes`` None (no keys) is segment 0."""
    codes = jnp.zeros(nrows, jnp.int32) if codes is None else to_device(codes)
    if keep is None:
        return frame, codes
    codes, datas, masks = _kept_first(keep, codes,
                                      [c.data for c in frame.columns],
                                      [c.mask for c in frame.columns])
    cols = [Column(d, c.domain, m, c.dictionary)
            for c, d, m in zip(frame.columns, datas, masks)]
    return Frame(cols, RangeLabels(nrows), frame.col_labels), codes


def _masked_groupby(blocks: list, stages: Sequence[alg.Stage], keys, aggs,
                    grid: str | None) -> PartitionedFrame | None:
    """Masked producer fusion: a fused groupby over uncompacted source
    blocks.  Each block sends the columns the chain reads to the device
    once, evaluates its selections there as a keep-mask (the compiled
    predicate program), runs its maps over those columns, and folds the
    mask into its group codes, so a rejected row reaches no statistic and
    no presence slot.  One pool round; no row take, no mask read back,
    nothing staged into the store.

    None, before any work, where the staged path must run: a chain or a
    key this path cannot run (``_masked_plan``), keys without a dense range
    over the source blocks (``_dense_keys``), or a grid the groupby would
    regroup — regrouping splits rows by count, and only the staged blocks
    split where the unfused plan does.  Each kept row meets the same rows
    in the same order in its segment as in the unfused plan."""
    if not blocks or preferred_row_parts(
            len(blocks), grid or GRID_PREFS["fused_groupby"]) != len(blocks):
        return None
    first = resolve(blocks[0]).induce()
    plan = _masked_plan(stages, keys, aggs, first)
    if plan is None:
        return None
    reads, src_keys = plan
    with phase("groupby:resolve"):
        frames = [first] + [resolve(b).induce() for b in blocks[1:]]
    dense = None
    if keys:
        with phase("groupby:keys"):
            dense = _dense_keys(frames, src_keys)
        if dense is None:
            return None
        count("groupby_dense")
    count("groupby_masked")
    G = math.prod(len(dk.values) for dk in dense) if dense else 1
    need = _agg_need(aggs)
    agg_cols = {c for c, _ in need}

    def block_partial(f: Frame) -> dict:
        codes = None
        if dense:
            with phase("groupby:keys"):
                codes = _dense_codes(f, src_keys, dense)
        with phase("groupby:stages"):
            cur, keep = _masked_stages(f, stages, reads)
            names = cur.col_labels.to_list()
            cur = cur.take_cols([j for j, n in enumerate(names) if n in agg_cols])
            cur, codes = _masked_partial_input(cur, keep, codes, f.nrows)
        return _block_partial(cur, codes, G, need, presence=bool(dense))

    partials = dispatch_blocks(block_partial, frames)
    if dense:
        need = need + [_PRESENCE]
    return _combine_finalize(partials, need, None, keys, aggs, G, dense=dense)


# ---- SORT ---------------------------------------------------------------
def _sort_perm(f: Frame, by: Sequence[Any], ascending: bool) -> np.ndarray:
    """The sort permutation: position i of the result comes from row idx[i]."""
    key_cols = []
    for v in _sort_rank_keys(f, by):
        # nulls (NaN) sort last regardless of direction
        v = np.where(np.isnan(v), np.inf if ascending else -np.inf, v)
        key_cols.append(v)
    if ascending:
        return np.lexsort(tuple(reversed(key_cols)))   # stable; first key primary
    return np.lexsort(tuple(-k for k in reversed(key_cols)))


def _sort(pf: PartitionedFrame, by: Sequence[Any], ascending: bool,
          stats=None) -> PartitionedFrame:
    from . import shuffle as _shuffle
    if _shuffle.enabled() and len(by):
        return _shuffle.shuffled_sort(pf, by, ascending, (), stats)
    return _sort_serial(pf, by, ascending, stats)


def _sort_serial(pf: PartitionedFrame, by: Sequence[Any], ascending: bool,
                 stats=None) -> PartitionedFrame:
    """The whole-frame oracle path (``REPRO_SHUFFLE=0``; also empty ``by``,
    which must raise exactly like ``np.lexsort(())``)."""
    f = pf.to_frame().induce()
    idx = _sort_perm(f, by, ascending)
    if stats is not None:
        stats.gather_rows += int(idx.shape[0])
    return _output_pf(f.take_rows(idx))


def _split_consumer_stages(stages: Sequence[alg.Stage]):
    """Split a consumer chain into (pushable predicates, gather projection,
    remaining stages).  Leading structured-``Expr`` selections are evaluated
    against the *pre-gather* frame (row-local predicates are permutation-
    invariant) and filter the gather index; an immediately following
    projection prunes the gathered columns.  Everything after the first
    MAP/RENAME (value/name changes) runs post-gather."""
    preds: list[alg.Expr] = []
    i = 0
    while (i < len(stages) and stages[i].op == "selection"
           and isinstance(stages[i].params["predicate"], alg.Expr)):
        preds.append(stages[i].params["predicate"])
        i += 1
    proj = None
    if i < len(stages) and stages[i].op == "projection":
        proj = stages[i].params["cols"]
        i += 1
    return preds, proj, stages[i:]


def _fused_sort(pf: PartitionedFrame, by: Sequence[Any], ascending: bool,
                stages: Sequence[alg.Stage], stats=None,
                grid: str | None = None) -> PartitionedFrame:
    from . import shuffle as _shuffle
    if _shuffle.enabled() and len(by):
        return _shuffle.shuffled_sort(pf, by, ascending, stages, stats,
                                      grid=grid)
    return _fused_sort_serial(pf, by, ascending, stages, stats)


def _fused_sort_serial(pf: PartitionedFrame, by: Sequence[Any],
                       ascending: bool, stages: Sequence[alg.Stage],
                       stats=None) -> PartitionedFrame:
    """Consumer fusion into SORT: selections filter the permutation *index*
    before the payload gather, so the materialized frame is built once,
    post-filter, instead of gathered-then-filtered."""
    f = pf.to_frame().induce()
    idx = _sort_perm(f, by, ascending)
    preds, proj, rest = _split_consumer_stages(stages)
    if preds:
        # evaluate on the UNSORTED frame (row-local ⇒ permutation-invariant):
        # no gather happens before the filter
        keep = np.asarray(_fused_selection_mask(preds, f), dtype=bool)
        idx = idx[keep[idx]]
    g = f.take_cols(f.col_labels.positions_of(proj)) if proj is not None else f
    if stats is not None:
        stats.gather_rows += int(idx.shape[0])
    out = _output_pf(g.take_rows(idx))
    if rest:
        out = out.map_blockwise(lambda b: _run_stages_block(b, rest))
    return out


# ---- WINDOW -------------------------------------------------------------
def _window_targets(frame: Frame, cols) -> list:
    if cols:
        return list(cols)
    return [n for n, c in zip(frame.col_labels.to_list(), frame.columns)
            if c.domain.is_numeric]


def _window(pf: PartitionedFrame, func: str, cols, size, periods,
            pre: Sequence[alg.Stage] = (), post: Sequence[alg.Stage] = (),
            grid: str | None = None) -> PartitionedFrame:
    """WINDOW, optionally with fused row-local chains: ``pre`` stages run in
    the same per-block program as the local scan, ``post`` stages in the same
    per-block program as the carry application (the carry combine sits between
    the two, exactly where the unfused path placed it).

    The working grid adapts to the pool width at plan time ("few_seams" —
    every partition boundary costs a carry composition / halo build, so the
    grid never oversubscribes the worker set by more than the coalescing
    slack).  Row-dropping pre-stages are staged on the *incoming* grid before
    the regroup: the unfused plan filters per incoming block and regrids the
    filtered result, so staging first is what keeps seam placement — and
    therefore carry composition — bit-identical between the two plans.
    Row-preserving pre-stages (elementwise map / projection / rename) are
    pointwise, so they stay fused into the scan program: regridding before or
    after them lands the seams on the same rows either way."""
    rp = preferred_row_parts(pf.row_parts, grid or GRID_PREFS["window"],
                             total_bytes=pf.nbytes())
    if rp != pf.row_parts and any(st.op == "selection" for st in pre):
        pf = pf.repartition(col_parts=1).map_blockwise(
            lambda b: _run_stages_block(b, pre))
        pre = ()
    pf = pf.repartition(row_parts=rp, col_parts=1)

    if func in ("cumsum", "cummax", "cummin", "cumprod"):
        # cumprod: per-block scan + multiplicative carry (kept exact — no
        # log-space trick)
        return _window_scan_blocks(pf, func, cols, pre, post)

    # halo/rolling paths need the staged blocks before the halo tails are
    # built; the producer chain still runs as ONE fused pool round
    if pre:
        pf = pf.map_blockwise(lambda b: _run_stages_block(b, pre))
    template = pf.parts[0][0].induce()
    targets = _window_targets(template, cols)

    if func in ("diff", "shift"):
        return _window_halo(pf, func, targets, periods, post)
    if func in ("rolling_sum", "rolling_mean"):
        assert size is not None, "rolling window requires size"
        # rolling(w) = cumsum − shift(cumsum, w); first w−1 rows are null
        csum = _window_scan_blocks(pf, "cumsum", targets)
        shifted = _window_halo(csum, "shift", targets, size)
        out = _rolling_combine(csum, shifted, targets, size,
                               mean=(func == "rolling_mean"))
        if post:
            out = out.map_blockwise(lambda b: _run_stages_block(b, post))
        return out
    raise ValueError(func)


def _apply_cols(frame: Frame, targets, fn: Callable[[Column], Column]) -> Frame:
    cols = list(frame.columns)
    names = frame.col_labels.to_list()
    for j, n in enumerate(names):
        if n in targets:
            cols[j] = fn(cols[j])
    return Frame(cols, frame.row_labels, frame.col_labels, frame.row_domains)


def _carry_combine(func: str, a, b):
    if func == "cumsum":
        return a + b
    if func == "cummax":
        return jnp.maximum(a, b)
    if func == "cummin":
        return jnp.minimum(a, b)
    return a * b   # cumprod


def _window_scan_blocks(pf: PartitionedFrame, func: str, cols,
                        pre: Sequence[alg.Stage] = (),
                        post: Sequence[alg.Stage] = ()) -> PartitionedFrame:
    """Blocked scan with cross-block carry composition, in two parallel
    per-block passes: (pre-stages + local scan + block total), then a tiny
    host-side exclusive combine of the totals, then (carry application +
    post-stages).  The scan ops are associative and commutative over the
    identity-filled values, so exclusive-combining the *local* totals is
    bitwise the same carry the old serial tail-chaining produced — and the
    carry application now runs block-parallel instead of serially."""
    blocks = [row[0] for row in pf.handles]

    def local(block):
        def scan_col(c: Column) -> Column:
            v = jnp.where(c.valid_mask(), c.data.astype(jnp.float32),
                          _scan_identity(func))
            if func == "cumprod":
                out = jnp.cumprod(v, axis=0)
            else:
                out = kops.window_scan(v, func)
            return Column(out.astype(jnp.float32), Domain.FLOAT, c.mask, None)

        def build(src):
            f = _run_stages_block(src, pre).induce() if pre else src.induce()
            targets = _window_targets(f, cols)
            return _apply_cols(f, targets, scan_col), targets

        with pinned(block) as src:
            scanned, targets = build(src)
            totals = ({n: scanned.col(n).data[-1] for n in targets}
                      if scanned.nrows else {})
            return (as_handle(scanned,
                              recompute=lambda: build(resolve(block))[0]),
                    totals, targets)

    locals_ = dispatch_blocks(local, blocks)

    # exclusive combine of block totals → per-block carries (host, tiny)
    carries: list[dict] = []
    acc: dict[Any, Any] = {}
    for _scanned, totals, _targets in locals_:
        carries.append(dict(acc))
        for n, t in totals.items():
            acc[n] = t if n not in acc else _carry_combine(func, acc[n], t)

    if not post and not any(carries):
        return PartitionedFrame([[item[0]] for item in locals_])

    def apply(args):
        (block, _totals, targets), carry = args

        def build(scanned):
            if carry:
                cols_ = list(scanned.columns)
                names = scanned.col_labels.to_list()
                for j, n in enumerate(names):
                    if n in targets and n in carry:
                        v = _carry_combine(func, cols_[j].data, carry[n])
                        cols_[j] = Column(v, cols_[j].domain, cols_[j].mask, None)
                scanned = Frame(cols_, scanned.row_labels, scanned.col_labels,
                                scanned.row_domains)
            return _run_stages_block(scanned, post) if post else scanned

        with pinned(block) as scanned:
            out = build(scanned)
            return block if out is scanned else as_handle(
                out, recompute=lambda: build(resolve(block)))

    out = dispatch_blocks(apply, list(zip(locals_, carries)))
    return PartitionedFrame([[b] for b in out])


def _scan_identity(func: str):
    return {"cumsum": 0.0, "cummax": -jnp.inf, "cummin": jnp.inf, "cumprod": 1.0}[func]


def _window_halo(pf: PartitionedFrame, func: str, targets, periods: int,
                 post: Sequence[alg.Stage] = ()) -> PartitionedFrame:
    """diff/shift via a ``periods``-row halo — the running tail of everything
    before the block (a single block may be shorter than ``periods``).
    ``post`` stages run inside the same per-block program."""
    blocks = [row[0] for row in pf.handles]

    # round 1 (parallel): induce each block ONCE and extract its tail — the
    # only rows that can ever reach a later block's halo.  The induced form
    # goes back into the store, so blocks are induced exactly once even when
    # the budget spills them between the rounds.
    def prep(h):
        with pinned(h) as raw:
            f = raw.induce()
            return (h if f is raw
                    else as_handle(f,
                                   recompute=lambda: resolve(h).induce())), \
                f.tail(periods)

    prepped = dispatch_blocks(prep, blocks)

    # serial compose of the tiny tails → per-block running halos (a block's
    # rows beyond its last ``periods`` can never appear in any halo, so
    # composing tails is exact — same recurrence the per-block sweep used)
    halos: list[Frame | None] = [None]
    running: Frame | None = None
    for _h, tail in prepped[:-1]:
        running = tail if running is None else (
            running.concat_rows(tail).tail(periods))
        halos.append(running)

    def local(args):
        (blk, _tail), halo = args
        with pinned(blk) as f:
            return as_handle(_halo_block(f, halo),
                             recompute=lambda: _halo_block(resolve(blk), halo))

    def _halo_block(block: Frame, halo: Frame | None) -> Frame:
        ext = halo.concat_rows(block) if halo is not None else block
        pad = ext.nrows - block.nrows

        def do(c_name) -> Column:
            c = ext.col(c_name)
            v = c.data.astype(jnp.float32)
            valid = c.valid_mask()
            prev = jnp.roll(v, periods)
            prev_valid = jnp.roll(valid, periods)
            rowpos = jnp.arange(ext.nrows)
            in_range = rowpos >= periods
            if func == "diff":
                out = v - prev
                mask = valid & prev_valid & in_range
            else:  # shift
                out = prev
                mask = prev_valid & in_range
            return Column(out[pad:], Domain.FLOAT, mask[pad:], None)

        cols = list(block.columns)
        names = block.col_labels.to_list()
        for j, n in enumerate(names):
            if n in targets:
                cols[j] = do(n)
        got = Frame(cols, block.row_labels, block.col_labels, block.row_domains)
        return _run_stages_block(got, post) if post else got

    out = dispatch_blocks(local, list(zip(prepped, halos)))
    return PartitionedFrame([[b] for b in out])


def _rolling_combine(csum: PartitionedFrame, shifted: PartitionedFrame, targets,
                     size: int, mean: bool) -> PartitionedFrame:
    rows = []
    offset = 0
    for (crow, srow) in zip(csum.parts, shifted.parts):
        cb, sb = crow[0], srow[0]
        cols = list(cb.columns)
        names = cb.col_labels.to_list()
        rowpos = jnp.arange(cb.nrows) + offset
        full = rowpos >= size - 1
        for j, n in enumerate(names):
            if n in targets:
                c, s = cb.col(n), sb.col(n)
                base = jnp.where(s.valid_mask(), s.data, 0.0)
                out = c.data - base
                if mean:
                    out = out / size
                cols[j] = Column(out, Domain.FLOAT, c.valid_mask() & full, None)
        rows.append([Frame(cols, cb.row_labels, cb.col_labels)])
        offset += cb.nrows
    return PartitionedFrame(rows)


# ---- TRANSPOSE ----------------------------------------------------------
def _transpose(pf: PartitionedFrame) -> PartitionedFrame:
    """Grid transpose: per-block kernel transpose + grid metadata swap."""
    def block_t(frame: Frame) -> Frame:
        # No induction: coded-ness is decidable from declared domains, and
        # UNSPECIFIED columns (a prior transpose's output) are numeric storage
        # whose logical schema is recovered via row_domains downstream.
        f = frame
        tgt = common_storage(f.schema)
        if tgt.is_coded:
            return _transpose_coded(f.induce())
        mat, dom = f.as_matrix(tgt if tgt is not Domain.UNSPECIFIED else Domain.FLOAT)
        out = kops.transpose(mat)
        masks = [c.mask for c in f.columns]
        out_mask = None
        if any(m is not None for m in masks):
            mm = jnp.stack([c.valid_mask() for c in f.columns], axis=1)
            out_mask = to_host(kops.transpose(mm))
        # Wide-output fast path ("billions of columns", paper §4.2): one
        # device→host materialization, then zero-copy numpy views per column —
        # NOT n_cols separate device slices (O(µs) dispatch each).
        out_np = to_host(out)
        # second-transpose schema recovery (paper §3.3): the child's recorded
        # row-type vector (length == child.nrows == our ncols) gives the
        # output schema without re-running S(·) over values.
        rec = f.row_domains if (f.row_domains is not None
                                and len(f.row_domains) == f.nrows) else None
        new_cols = []
        for i in range(f.nrows):
            dom = rec[i] if rec is not None else Domain.UNSPECIFIED
            data = out_np[:, i]
            if rec is not None:
                data = data.astype(storage_dtype(dom))
            new_cols.append(Column(
                data, dom,
                None if out_mask is None else out_mask[:, i],
                None))
        return Frame(new_cols, f.col_labels, f.row_labels, row_domains=f.schema)

    return pf.transpose_grid(block_t)


def _transpose_coded(f: Frame) -> Frame:
    """Heterogeneous/string transpose: host re-encode (paper: coerce to
    Object; schema induction recovers on a second transpose)."""
    records = f.to_records()
    rec = f.row_domains if (f.row_domains is not None
                            and len(f.row_domains) == f.nrows) else None
    new_cols = []
    for i in range(f.nrows):
        vals = [records[i][j] for j in range(f.ncols)]
        if rec is not None:
            new_cols.append(_host_column(vals, rec[i]))
        else:
            new_cols.append(_host_column(
                [None if v is None else str(v) for v in vals], Domain.STR))
    return Frame(new_cols, f.col_labels, f.row_labels, row_domains=f.schema)


# ---- MAP ------------------------------------------------------------------
def _apply_udf_block(frame: Frame, udf: alg.Udf) -> Frame:
    """Run a Udf over one block (also the per-stage body of fused pipelines)."""
    f = frame.induce()
    cols_in = {n: c for n, c in zip(f.col_labels.to_list(), f.columns)}
    out = udf.fn(cols_in, f)
    if isinstance(out, Frame):
        return out
    # dict {label: Column | array | (array, mask)} preserving row count
    names, cols = [], []
    for name, v in out.items():
        names.append(name)
        if isinstance(v, Column):
            cols.append(v)
        elif isinstance(v, tuple):
            data, mask = v
            cols.append(Column(to_device(data), _infer_dom(data), mask, None))
        else:
            arr = to_device(v)
            cols.append(Column(arr, _infer_dom(arr), None, None))
    return Frame(cols, f.row_labels, labels_from_values(names))


def _map(pf: PartitionedFrame, udf: alg.Udf) -> PartitionedFrame:
    if udf.elementwise:
        return pf.repartition(col_parts=1).map_blockwise(
            lambda f: _apply_udf_block(f, udf))
    return PartitionedFrame.from_frame(_apply_udf_block(pf.to_frame(), udf))


def _infer_dom(arr) -> Domain:
    d = to_device(arr).dtype
    if d == jnp.bool_:
        return Domain.BOOL
    if jnp.issubdtype(d, jnp.integer):
        return Domain.INT
    return Domain.FLOAT


# ---- label movement ---------------------------------------------------------
def _to_labels(pf: PartitionedFrame, column: Any) -> PartitionedFrame:
    def conv(frame: Frame) -> Frame:
        f = frame.induce()
        j = f.col_labels.position_of(column)
        c = f.columns[j]
        labels = labels_from_values(c.to_pylist(), c.domain)
        keep = [x for x in range(f.ncols) if x != j]
        g = f.take_cols(keep)
        return Frame(g.columns, labels, g.col_labels)
    return pf.repartition(col_parts=1).map_blockwise(conv)


def _from_labels(pf: PartitionedFrame, label: Any) -> PartitionedFrame:
    pf = pf.repartition(col_parts=1)
    offsets = pf.row_block_offsets()

    def conv(args):
        (block, start) = args

        def build(f):
            vals = f.row_labels.to_list()
            c = _host_column(vals, Domain.INT if isinstance(f.row_labels, (RangeLabels, IntLabels)) else None)
            return Frame([c] + list(f.columns),
                         RangeLabels(f.nrows, start),
                         labels_from_values([label]).concat(f.col_labels))

        with pinned(block) as f:
            return as_handle(build(f), recompute=lambda: build(resolve(block)))

    out = dispatch_blocks(conv, [(row[0], offsets[i])
                                 for i, row in enumerate(pf.handles)])
    return PartitionedFrame([[b] for b in out])


def _rename_block(frame: Frame, mapping: dict) -> Frame:
    names = [mapping.get(n, n) for n in frame.col_labels.to_list()]
    return Frame(frame.columns, frame.row_labels, labels_from_values(names), frame.row_domains)


def _rename(pf: PartitionedFrame, mapping_items) -> PartitionedFrame:
    mapping = dict(mapping_items)
    return pf.map_blockwise(lambda frame: _rename_block(frame, mapping))


def _limit(pf: PartitionedFrame, k: int, tail: bool) -> PartitionedFrame:
    # Touch only the row blocks the prefix/suffix needs (§6.1.2).
    if not tail:
        f = pf.prefix(k).to_frame()
        return PartitionedFrame.from_frame(f.head(k))
    need, keep = k, []
    for i in range(pf.row_parts - 1, -1, -1):
        keep.insert(0, pf.handles[i])
        need -= pf.handles[i][0].nrows
        if need <= 0:
            break
    f = PartitionedFrame(keep).to_frame()
    return PartitionedFrame.from_frame(f.tail(k))


# ---- rewrite targets: column-space ops without any TRANSPOSE (paper §5) ------
def _key_rows_matrix(pf: PartitionedFrame, row_names: Sequence[Any]) -> np.ndarray:
    """(len(row_names), ncols) float64 matrix of the named rows' values."""
    pf1 = pf.repartition(col_parts=1)
    offsets = pf1.row_block_offsets()
    rows = []
    for name in row_names:
        found = None
        for bi, row in enumerate(pf1.parts):
            try:
                local = row[0].row_labels.position_of(name)
                found = (bi, local)
                break
            except KeyError:
                continue
        if found is None:
            raise KeyError(name)
        bi, local = found
        one = pf1.parts[bi][0].take_rows(np.asarray([local]))
        rows.append(_row_keys(one.induce(), None)[0])
    return np.stack(rows, axis=0)


def _column_sort(pf: PartitionedFrame, by: Sequence[Any], ascending: bool) -> PartitionedFrame:
    keys = _key_rows_matrix(pf, by)                       # (K, n)
    if ascending:
        perm = np.lexsort(tuple(reversed([k for k in keys])))
    else:
        perm = np.lexsort(tuple(reversed([-k for k in keys])))
    pf1 = pf.repartition(col_parts=1)
    return pf1.map_blockwise(lambda f: f.take_cols(perm.tolist()))


def _column_filter(pf: PartitionedFrame, predicate: alg.Expr) -> PartitionedFrame:
    refs = sorted(predicate.refs(), key=repr)
    keys = _key_rows_matrix(pf, refs)                     # (K, n)
    n = keys.shape[1]
    temp = Frame(
        [Column(to_device(keys[i].astype(np.float32)), Domain.FLOAT) for i in range(len(refs))],
        RangeLabels(n),
        labels_from_values(list(refs)),
    )
    keep = _predicate_mask(temp, predicate)
    idx = np.nonzero(keep)[0].tolist()
    pf1 = pf.repartition(col_parts=1)
    return pf1.map_blockwise(lambda f: f.take_cols(idx))


# =============================================================================
# FUSED PIPELINE (paper §5): one per-block program for a row-local chain
# =============================================================================
def _eval_expr_env(expr: alg.Expr, env: dict) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``eval_expr`` over a plain {name: (values, mask)} environment — the
    jit-traceable entry used by compiled predicate chains (no Frame objects,
    no coded columns; callers gate on that).  Same interpreter core as
    ``eval_expr``, so fused and unfused predicates cannot diverge."""
    nrows = next(iter(env.values()))[0].shape[0]
    return _eval_expr_core(expr, env.__getitem__, nrows)


# Compiled predicate-chain programs, keyed by the combined expression's
# structural key.  One XLA executable evaluates the whole chain → bool keep
# mask; jit's own shape cache handles the (±1-row) block-size variants.
# Bounded FIFO: predicates with varying literals each get a distinct key, so
# an unbounded dict would leak one compiled program per literal seen.
_PRED_JIT: dict[tuple, Callable] = {}
_PRED_JIT_LOCK = threading.Lock()
_PRED_JIT_MAX = 256


def _compiled_predicate(expr: alg.Expr, refs: tuple) -> Callable:
    key = expr.key()
    with _PRED_JIT_LOCK:
        fn = _PRED_JIT.get(key)
        if fn is None:
            def prog(datas, masks):
                env = {r: (d, jnp.ones(d.shape[0], jnp.bool_) if m is None else m)
                       for r, d, m in zip(refs, datas, masks)}
                v, mask = _eval_expr_env(expr, env)
                return v.astype(jnp.bool_) & mask
            while len(_PRED_JIT) >= _PRED_JIT_MAX:
                _PRED_JIT.pop(next(iter(_PRED_JIT)))
            fn = _PRED_JIT[key] = jax.jit(prog)
    return fn


def _predicate_operands(preds: Sequence[alg.Expr], frame: Frame) -> tuple:
    """The AND of a run of structured predicates, with the (refs, columns)
    its compiled program takes, or (combined, None, None) where the
    interpreted path must evaluate it.

    ANDing before filtering is exact: predicates are row-local, so a row
    removed by an earlier selection contributes False to the conjunction
    regardless of its later-predicate value."""
    combined = preds[0]
    for p in preds[1:]:
        combined = alg.BinExpr("&", combined, p)
    refs = tuple(sorted(combined.refs(), key=repr))
    if not refs:
        return combined, None, None
    try:
        cols = [frame.col(r) for r in refs]
    except KeyError:
        return combined, None, None
    if any(c.domain.is_coded for c in cols):
        # coded columns need host code-table translation → interpreted path
        return combined, None, None
    if any(c.domain is Domain.INT and c.data.dtype.itemsize > 4
           for c in cols) or _has_wide_lit(combined):
        # wide int64 host columns / out-of-int32 literals would truncate (or
        # fail to trace) through the jit boundary (no x64): the interpreted
        # path handles them in 64-bit host arithmetic.  dtype check on the
        # array object itself — np.asarray here would device-transfer every
        # predicate column on an accelerator backend.
        return combined, None, None
    return combined, refs, cols


def _fused_selection_mask(preds: Sequence[alg.Expr], frame: Frame) -> np.ndarray:
    """keep-mask for a run of structured predicates, as ONE device program,
    read back to the host for the row take."""
    combined, refs, cols = _predicate_operands(preds, frame)
    if cols is None:
        return _predicate_mask(frame, combined)
    fn = _compiled_predicate(combined, refs)
    datas, masks = [c.data for c in cols], [c.valid_mask() for c in cols]
    note_h2d(*datas, *masks)
    return to_host(fn(datas, masks))


def _selection_keep(preds: Sequence[alg.Expr], frame: Frame) -> jax.Array:
    """The same keep-mask left on the device, for a consumer that masks
    rows instead of taking them; a column without a mask passes None, so
    no all-valid mask is sent."""
    combined, refs, cols = _predicate_operands(preds, frame)
    if cols is None:
        v, mask = eval_expr(combined, frame)
        _note_mixed(v, mask)
        keep = v.astype(jnp.bool_) & mask     # null comparisons → False
        return to_device(keep) if isinstance(keep, np.ndarray) else keep
    datas, masks = [c.data for c in cols], [c.mask for c in cols]
    note_h2d(*datas, *masks)
    return _compiled_predicate(combined, refs)(datas, masks)


# Compiled map-run programs: a run of consecutive elementwise MAP stages
# traced as ONE XLA program per (udf chain, input schema).  Value None marks a
# chain that failed to trace (host-side numpy, data-dependent structure, ...)
# or whose traced output diverged from the eager path on the probe block —
# those chains stay on eager per-stage dispatch.  Bounded FIFO like _PRED_JIT.
_MAP_JIT: dict[tuple, tuple | None] = {}
_MAP_JIT_LOCK = threading.Lock()
_MAP_JIT_MAX = 128
_MAP_JIT_MISS = object()


def map_jit_counts() -> dict[str, int]:
    """Map chains currently cached as one traced program (``adopted``) and as
    eager per-stage dispatch after a failed or divergent probe
    (``fell_back``).  Each fallback's cause is logged at INFO level."""
    with _MAP_JIT_LOCK:
        adopted = sum(e is not None for e in _MAP_JIT.values())
        return {"adopted": adopted, "fell_back": len(_MAP_JIT) - adopted}


def _run_map_stages_eager(frame: Frame, udfs: Sequence[alg.Udf]) -> Frame:
    cur = frame
    for u in udfs:
        cur = _apply_udf_block(cur, u)
    return cur


def _jit_udfs_enabled() -> bool:
    """Same dispatch policy as ``kernels.ops.use_pallas``: on CPU the host
    numpy eager path is the tuned one (a per-block XLA dispatch plus the
    pass-through column round-trips costs more than the memcpy-level work it
    replaces); on an accelerator the one-program-per-chain form wins.  Set
    ``REPRO_JIT_UDFS=1`` to force jit-traced map runs anywhere, ``=0`` to
    force eager anywhere."""
    flag = os.environ.get("REPRO_JIT_UDFS", "")
    if flag == "0":
        return False
    if flag:
        return True
    return jax.default_backend() != "cpu"


def _map_run_program(udfs: Sequence[alg.Udf], names: tuple, domains: tuple):
    """jit-traced whole-chain map run over a plain (datas, masks) environment.
    Output metadata (names/domains/mask-presence) is captured at trace time —
    static for an elementwise chain, or the trace fails and we fall back."""
    meta: dict = {}

    def prog(datas, masks):
        n = int(datas[0].shape[0])
        cols = [Column(d, dom, m, None)
                for d, dom, m in zip(datas, domains, masks)]
        f = Frame(cols, RangeLabels(n), labels_from_values(list(names)))
        for u in udfs:
            f = _apply_udf_block(f, u)
        meta["names"] = f.col_labels.to_list()
        meta["domains"] = tuple(c.domain for c in f.columns)
        return (tuple(c.data for c in f.columns),
                tuple(c.mask for c in f.columns))

    return jax.jit(prog), meta


def _frames_bit_equal(a: Frame, b: Frame) -> bool:
    if a.col_labels.to_list() != b.col_labels.to_list():
        return False
    if a.row_labels.to_list() != b.row_labels.to_list():
        return False
    for ca, cb in zip(a.columns, b.columns):
        if ca.domain is not cb.domain:
            return False
        va, vb = to_host(ca.valid_mask()), to_host(cb.valid_mask())
        if not np.array_equal(va, vb):
            return False
        da, db = to_host(ca.data), to_host(cb.data)
        if da.dtype != db.dtype:
            return False
        if not np.array_equal(np.where(va, da, 0), np.where(vb, db, 0)):
            return False
    return True


def _run_map_stages(frame: Frame, udfs: Sequence[alg.Udf]) -> Frame:
    """Run a consecutive run of elementwise MAP stages over one block as one
    XLA program when the chain traces; per-chain eager fallback otherwise.
    The first block through a chain is executed BOTH ways and compared — the
    compiled program is only adopted if it reproduces the eager result
    bit-for-bit, so fused and unfused plans can never diverge."""
    f = frame.induce()
    if not _jit_udfs_enabled():
        return _run_map_stages_eager(f, udfs)
    names = f.col_labels.to_list()
    domains = tuple(c.domain for c in f.columns)
    if any(d.is_coded for d in domains):
        return _run_map_stages_eager(f, udfs)
    key = (tuple(u.key() for u in udfs), tuple(names), domains,
           tuple(c.mask is None for c in f.columns))
    try:
        hash(key)
    except TypeError:   # unhashable labels
        return _run_map_stages_eager(f, udfs)

    with _MAP_JIT_LOCK:
        entry = _MAP_JIT.get(key, _MAP_JIT_MISS)
    if entry is None:
        return _run_map_stages_eager(f, udfs)

    datas = [c.data for c in f.columns]
    masks = [c.mask for c in f.columns]
    note_h2d(*datas, *masks)     # the compiled chain's host operands

    if entry is not _MAP_JIT_MISS:
        fn, meta = entry
        out_datas, out_masks = fn(datas, masks)
        cols = [Column(d, dom, m, None)
                for d, dom, m in zip(out_datas, meta["domains"], out_masks)]
        return Frame(cols, f.row_labels, labels_from_values(meta["names"]))

    # probe: trace, run, and verify against the eager path on this block
    eager = _run_map_stages_eager(f, udfs)
    entry = None
    try:
        fn, meta = _map_run_program(udfs, tuple(names), domains)
        out_datas, out_masks = fn(datas, masks)
        cols = [Column(d, dom, m, None)
                for d, dom, m in zip(out_datas, meta["domains"], out_masks)]
        traced = Frame(cols, f.row_labels, labels_from_values(meta["names"]))
        if _frames_bit_equal(eager, traced):
            entry = (fn, meta)
        else:
            _log.info("map chain %s: traced result differs from eager; "
                      "kept eager", key[0])
    except Exception:   # any trace failure: the chain stays eager
        _log.info("map chain %s failed to trace; kept eager", key[0],
                  exc_info=True)
        entry = None
    with _MAP_JIT_LOCK:
        while len(_MAP_JIT) >= _MAP_JIT_MAX:
            _MAP_JIT.pop(next(iter(_MAP_JIT)))
        _MAP_JIT[key] = entry
    return eager


def _run_stages_block(frame: Frame, stages: Sequence[alg.Stage]) -> Frame:
    """Execute a row-local stage chain over ONE block: the shared per-block
    program body of FusedPipeline and of every barrier-fused operator."""
    cur = frame
    i = 0
    while i < len(stages):
        st = stages[i]
        if st.op == "selection":
            # coalesce a run of structured-Expr selections → one jit mask
            preds = []
            while (i < len(stages) and stages[i].op == "selection"
                   and isinstance(stages[i].params["predicate"], alg.Expr)):
                preds.append(stages[i].params["predicate"])
                i += 1
            with phase("stage:select"):
                if preds:
                    cur = cur.filter_rows(_fused_selection_mask(preds, cur))
                else:  # opaque Udf predicate
                    cur = cur.filter_rows(
                        _predicate_mask(cur, st.params["predicate"]))
                    i += 1
        elif st.op == "map":
            # coalesce a run of elementwise maps → one jit-traced program
            udfs = []
            while i < len(stages) and stages[i].op == "map":
                udfs.append(stages[i].params["udf"])
                i += 1
            with phase("stage:map"):
                cur = _run_map_stages(cur, udfs)
        elif st.op == "projection":
            cur = _project_block(cur, st.params["cols"])
            i += 1
        elif st.op == "rename":
            cur = _rename_block(cur, dict(st.params["mapping"]))
            i += 1
        else:
            raise ValueError(f"non-fusible stage {st.op}")
    return cur


def _run_fused(pf: PartitionedFrame, stages: Sequence[alg.Stage]) -> PartitionedFrame:
    """Execute a fused row-local chain: one sweep per row partition, values
    staying on device across stages, one pool dispatch for the whole chain."""
    pf1 = pf.repartition(col_parts=1)
    return pf1.map_blockwise(lambda f: _run_stages_block(f, stages))


# =============================================================================
# dispatcher
# =============================================================================
def run_node(node: alg.Node, inputs: list[PartitionedFrame],
             stats=None) -> PartitionedFrame:
    """Dispatch one plan node.  ``stats`` (duck-typed ``ExecStats``) receives
    physical-level counters — ``gather_rows``, the payload rows gathered /
    materialized by SORT/JOIN/DIFFERENCE/DROP-DUPLICATES (the fused-consumer
    paths gather strictly fewer rows than their unfused counterparts on
    selective chains), and ``dedup_blocks`` / ``dedup_key_rows``, the blocks
    and rows the block-parallel dedup key extraction processed."""
    op = node.op
    if op == "fused_pipeline":
        return _run_fused(inputs[0], node.params["stages"])
    if op == "fused_groupby":
        return _fused_groupby(inputs[0], node.params["stages"],
                              node.params["keys"], node.params["aggs"],
                              node.params.get("grid"))
    if op == "fused_sort":
        return _fused_sort(inputs[0], node.params["by"], node.params["ascending"],
                           node.params["stages"], stats,
                           grid=node.params.get("grid"))
    if op == "fused_join":
        return _fused_join(inputs[0], inputs[1], node.params,
                           node.params["stages"], stats)
    if op == "fused_window":
        return _window(inputs[0], node.params["func"], node.params["cols"],
                       node.params["size"], node.params["periods"],
                       node.params["pre_stages"], node.params["post_stages"],
                       grid=node.params.get("grid"))
    if op == "fused_difference":
        return _difference(inputs[0], inputs[1], stats,
                           node.params["pre_stages"],
                           node.params["right_pre_stages"],
                           node.params["post_stages"],
                           grid=node.params.get("grid"))
    if op == "fused_drop_duplicates":
        return _drop_duplicates(inputs[0], node.params["subset"], stats,
                                node.params["pre_stages"],
                                node.params["post_stages"],
                                grid=node.params.get("grid"))
    if op == "selection":
        return _selection(inputs[0], node.params["predicate"])
    if op == "projection":
        return _projection(inputs[0], node.params["cols"])
    if op == "union":
        return _union(inputs[0], inputs[1])
    if op == "difference":
        return _difference(inputs[0], inputs[1], stats)
    if op == "join":
        return _join(inputs[0], inputs[1], node.params, stats)
    if op == "drop_duplicates":
        return _drop_duplicates(inputs[0], node.params["subset"], stats)
    if op == "groupby":
        return _groupby(inputs[0], node.params["keys"], node.params["aggs"])
    if op == "sort":
        return _sort(inputs[0], node.params["by"], node.params["ascending"], stats)
    if op == "rename":
        return _rename(inputs[0], node.params["mapping"])
    if op == "window":
        return _window(inputs[0], node.params["func"], node.params["cols"],
                       node.params["size"], node.params["periods"])
    if op == "transpose":
        return _transpose(inputs[0])
    if op == "map":
        return _map(inputs[0], node.params["udf"])
    if op == "to_labels":
        return _to_labels(inputs[0], node.params["column"])
    if op == "from_labels":
        return _from_labels(inputs[0], node.params["label"])
    if op == "limit":
        return _limit(inputs[0], node.params["k"], node.params["tail"])
    if op == "column_sort":
        return _column_sort(inputs[0], node.params["by"], node.params["ascending"])
    if op == "column_filter":
        return _column_filter(inputs[0], node.params["predicate"])
    raise ValueError(f"no physical implementation for {op}")
