"""Vectorized JAX predicate kernels over typed column pages.

One jitted function per (plan signature, page shape): the compiled
program tree (:mod:`.plan`) is traced into element-wise jnp ops over
the pager's fixed-shape buffers, producing a boolean row mask
``[B, R]`` — WHICH rows pass the WHERE clause. Emission of the passing
rows stays on host through the CPU evaluator's own serializer, so the
response bytes are identical by construction; the device does the
O(rows) byte-crunching (the paper's offload delta applied to the
analytics read path).

Semantics reproduce ``s3select.sql`` exactly:

  * comparisons take the evaluator's per-row coercion: numeric when
    BOTH sides parse as numbers (IEEE float64 — the kernels run under
    a local ``enable_x64`` scope so 1.1 means the same 64-bit value
    the CPU compares), False when either side is null, else
    lexicographic compare of the ``str()`` forms (UTF-8 bytes order ==
    code-point order; the zero pad byte sorts below every real byte,
    which is why the pager declines cells containing NUL);
  * arithmetic propagates "None" (non-numeric operand, division by
    zero) into a False comparison, like the evaluator;
  * LIKE supports exact / prefix / suffix / contains shapes on the
    ``str()`` form with per-row lengths.

Batches pad to the next power of two along the page axis so the jit
cache sees a handful of shapes, not one per request size.

Env:
  MINIO_TPU_SCAN_DEVICE=on|off|force   "on" (default) rides the device
      where it reproduces the evaluator: an XLA backend with real
      float64 that the erasure verbs route to (a forced mesh); on a TPU
      it declines up front (`no-f64`, see decline_reason). "force" runs
      the kernels on any XLA backend (tests, benches); "off" disables
      the device path entirely.
"""

from __future__ import annotations

import collections
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import device, knobs

_COMPILE_MU = threading.Lock()
# (signature, shape) -> jitted fn. Bounded LRU: the signature bakes in
# query literals, so per-request values (timestamps, uuids) would grow
# the trace cache without bound on a long-running server.
_KERNELS: collections.OrderedDict = collections.OrderedDict()
_KERNEL_CACHE_CAP = knobs.get_int("MINIO_TPU_SCAN_KERNEL_CACHE")


def decline_reason() -> str:
    """Why the scan plane declines up front on this process ("" when it
    serves) — the erasure verbs' discipline: no device, no reason to
    pay the dispatch seam, unless forced (tests/bench)."""
    mode = knobs.get_str("MINIO_TPU_SCAN_DEVICE").lower()
    if mode in ("off", "0", "false", "no"):
        return "no-device"
    if mode == "force":
        return ""
    if device.probe().is_tpu:
        # the kernels need IEEE float64 to agree with the evaluator
        # and the TPU has none: XLA emulates f64 there, not exactly —
        # on a v5e, ulp-equality, `a * 3 <= c` and `b % 7 = 3`
        # predicates selected different rows than the CPU evaluator
        # (chip run, PR 21). Keep/fix/delete is ROADMAP A10's call.
        return "no-f64"
    from ..object.codec import _device_is_tpu, _mesh_active
    if not _device_is_tpu() and _mesh_active() is None:
        return "no-device"
    return ""


def device_allowed() -> bool:
    return not decline_reason()


# -- trace-time helpers -----------------------------------------------------

class _Val:
    """One comparison side evaluated over the page: float value +
    numeric/null flags, plus the str() form as (bytes[B,R,W], len) —
    None for arithmetic results (their string path is declined
    upstream)."""

    __slots__ = ("num", "ok", "null", "sb", "slen")

    def __init__(self, num, ok, null, sb=None, slen=None):
        self.num, self.ok, self.null = num, ok, null
        self.sb, self.slen = sb, slen


def _const_str(jnp, shape, needle: bytes, width: int):
    """A literal's str() form broadcast to [B,R,width]."""
    w = max(width, len(needle), 1)
    buf = np.zeros(w, np.uint8)
    if needle:
        buf[:len(needle)] = np.frombuffer(needle, np.uint8)
    sb = jnp.broadcast_to(jnp.asarray(buf), (*shape, w))
    slen = jnp.full(shape, len(needle), np.int32)
    return sb, slen


def _pad_w(jnp, sb, w):
    """Zero-pad the byte axis to width w (trace-time static)."""
    have = sb.shape[-1]
    if have >= w:
        return sb
    pad = [(0, 0)] * (sb.ndim - 1) + [(0, w - have)]
    return jnp.pad(sb, pad)


def _str_cmp(jnp, op: str, a: _Val, b: _Val):
    """Lexicographic compare of the str() forms (zero-padded byte
    arrays: pad < every real byte, so prefix-shorter sorts first,
    exactly like Python str compare on the code points)."""
    w = max(a.sb.shape[-1], b.sb.shape[-1])
    ab = _pad_w(jnp, a.sb, w)
    bb = _pad_w(jnp, b.sb, w)
    diff = ab != bb
    any_diff = jnp.any(diff, axis=-1)
    first = jnp.argmax(diff, axis=-1)
    av = jnp.take_along_axis(ab, first[..., None], axis=-1)[..., 0]
    bv = jnp.take_along_axis(bb, first[..., None], axis=-1)[..., 0]
    lt = any_diff & (av < bv)
    eq = ~any_diff
    if op == "=":
        return eq
    if op in ("!=", "<>"):
        return ~eq
    if op == "<":
        return lt
    if op == "<=":
        return lt | eq
    if op == ">":
        return ~(lt | eq)
    return ~lt                                   # ">="


def _num_cmp(jnp, op: str, a, b):
    if op == "=":
        return a == b
    if op in ("!=", "<>"):
        return a != b
    if op == "<":
        return a < b
    if op == "<=":
        return a <= b
    if op == ">":
        return a > b
    return a >= b


def _eval_side(jnp, side: tuple, arrs: dict, shape, width: int) -> _Val:
    kind = side[0]
    if kind == "col":
        j = side[1]
        return _Val(arrs["num"][:, :, j], arrs["ok"][:, :, j],
                    arrs["null"][:, :, j], arrs["sb"][:, :, j, :],
                    arrs["slen"][:, :, j])
    if kind == "nlit":
        _k, value, sform = side
        sb, slen = _const_str(jnp, shape, sform, width)
        f = jnp.bool_(False)
        return _Val(jnp.full(shape, value, jnp.float64),
                    jnp.broadcast_to(~f, shape),
                    jnp.broadcast_to(f, shape), sb, slen)
    if kind == "slit":
        b = side[1]
        sb, slen = _const_str(jnp, shape, b, width)
        nv = None
        try:
            nv = float(b.decode("utf-8"))
        except ValueError:
            pass
        f = jnp.bool_(False)
        return _Val(jnp.full(shape, nv if nv is not None else 0.0,
                             jnp.float64),
                    jnp.broadcast_to(jnp.bool_(nv is not None), shape),
                    jnp.broadcast_to(f, shape), sb, slen)
    # arithmetic: numeric-only; invalid (non-numeric operand or
    # division/modulo by zero) behaves like the evaluator's None
    _k, op, sa, sb_ = side
    a = _eval_side(jnp, sa, arrs, shape, width)
    b = _eval_side(jnp, sb_, arrs, shape, width)
    valid = a.ok & b.ok
    if op == "+":
        v = a.num + b.num
    elif op == "-":
        v = a.num - b.num
    elif op == "*":
        v = a.num * b.num
    elif op == "/":
        valid = valid & (b.num != 0)
        v = a.num / jnp.where(b.num == 0, 1.0, b.num)
    else:                                        # "%" — Python floor-mod
        valid = valid & (b.num != 0)
        v = jnp.mod(a.num, jnp.where(b.num == 0, 1.0, b.num))
    return _Val(v, valid, ~valid)


def _eval_cmp(jnp, op: str, a: _Val, b: _Val):
    both_num = a.ok & b.ok
    either_null = a.null | b.null
    rnum = _num_cmp(jnp, op, a.num, b.num)
    if a.sb is None or b.sb is None:
        # an arithmetic side: its string path was declined upstream,
        # and the columns it compares against are numeric-or-null
        return both_num & rnum
    rstr = _str_cmp(jnp, op, a, b)
    return jnp.where(both_num, rnum, (~either_null) & rstr)


def _eval_like(jnp, arrs, slot: int, kind: str, needle: bytes,
               negate: bool):
    sb = arrs["sb"][:, :, slot, :]
    slen = arrs["slen"][:, :, slot]
    null = arrs["null"][:, :, slot]
    W = sb.shape[-1]
    L = len(needle)
    if kind == "any":
        ok = jnp.broadcast_to(jnp.bool_(True), null.shape)
    elif L > W:
        ok = jnp.broadcast_to(jnp.bool_(False), null.shape)
    else:
        nd = jnp.asarray(np.frombuffer(needle, np.uint8))
        if kind == "exact":
            ok = (slen == L) & jnp.all(sb[..., :L] == nd, axis=-1)
        elif kind == "prefix":
            ok = (slen >= L) & jnp.all(sb[..., :L] == nd, axis=-1)
        elif kind == "suffix":
            idx = jnp.clip(slen[..., None] - L, 0, W - 1) \
                + jnp.arange(L)
            tail = jnp.take_along_axis(sb, idx, axis=-1)
            ok = (slen >= L) & jnp.all(tail == nd, axis=-1)
        else:                                    # contains
            hits = []
            for off in range(W - L + 1):
                hits.append(jnp.all(sb[..., off:off + L] == nd,
                                    axis=-1)
                            & (slen >= off + L))
            ok = jnp.any(jnp.stack(hits, axis=-1), axis=-1)
    ok = ok & ~null                              # NULL never matches
    return ok != negate if negate else ok


def _eval_prog(jnp, prog: tuple, arrs: dict, shape, width: int):
    kind = prog[0]
    if kind == "true":
        return jnp.broadcast_to(jnp.bool_(True), shape)
    if kind == "and":
        return _eval_prog(jnp, prog[1], arrs, shape, width) \
            & _eval_prog(jnp, prog[2], arrs, shape, width)
    if kind == "or":
        return _eval_prog(jnp, prog[1], arrs, shape, width) \
            | _eval_prog(jnp, prog[2], arrs, shape, width)
    if kind == "not":
        return ~_eval_prog(jnp, prog[1], arrs, shape, width)
    if kind == "cmp":
        _k, op, sa, sb = prog
        return _eval_cmp(jnp, op,
                         _eval_side(jnp, sa, arrs, shape, width),
                         _eval_side(jnp, sb, arrs, shape, width))
    if kind == "in":
        _k, sx, items, negate = prog
        x = _eval_side(jnp, sx, arrs, shape, width)
        hit = jnp.broadcast_to(jnp.bool_(False), shape)
        for item in items:
            iv = _eval_side(jnp, item, arrs, shape, width)
            hit = hit | _eval_cmp(jnp, "=", x, iv)
        return ~hit if negate else hit
    if kind == "between":
        _k, sx, slo, shi, negate = prog
        x = _eval_side(jnp, sx, arrs, shape, width)
        lo = _eval_side(jnp, slo, arrs, shape, width)
        hi = _eval_side(jnp, shi, arrs, shape, width)
        ok = (~x.null) & _eval_cmp(jnp, ">=", x, lo) \
            & _eval_cmp(jnp, "<=", x, hi)
        return ~ok if negate else ok
    if kind == "isnull":
        _k, slot, negate = prog
        null = arrs["null"][:, :, slot]
        return ~null if negate else null
    if kind == "like":
        _k, slot, lkind, needle, negate = prog
        return _eval_like(jnp, arrs, slot, lkind, needle, negate)
    raise ValueError(f"bad scan program node {kind!r}")


# -- entry points -----------------------------------------------------------

_ARRAY_ORDER = ("num", "ok", "null", "sb", "slen", "rowvalid")


def _kernel_for(plan, shape: tuple):
    key = (plan.signature, shape)
    with _COMPILE_MU:
        fn = _KERNELS.get(key)
        if fn is not None:
            _KERNELS.move_to_end(key)
            return fn
        prog = plan.prog

        def run(num, ok, null, sb, slen, rowvalid):
            arrs = {"num": num, "ok": ok, "null": null, "sb": sb,
                    "slen": slen, "rowvalid": rowvalid}
            mask = _eval_prog(jnp, prog, arrs, num.shape[:2],
                              sb.shape[-1])
            return mask & rowvalid

        fn = jax.jit(run)
        _KERNELS[key] = fn
        while len(_KERNELS) > _KERNEL_CACHE_CAP:
            _KERNELS.popitem(last=False)
        return fn


def _pad_batch(arrays: dict, b: int) -> dict:
    """Pad the page axis to b (power-of-two cap) so the jit cache sees
    a handful of batch shapes; pad pages carry rowvalid=False."""
    have = next(iter(arrays.values())).shape[0]
    if have == b:
        return arrays
    out = {}
    for k, v in arrays.items():
        pad = np.zeros((b - have, *v.shape[1:]), v.dtype)
        if k == "null":
            pad[:] = True
        out[k] = np.concatenate([v, pad], axis=0)
    return out


def run_batch(plan, arrays: dict) -> np.ndarray:
    """Evaluate the plan's predicate over one (possibly coalesced)
    page batch; returns the boolean row mask [B, R]. Raises on any
    backend failure — callers treat that as a decline and CPU-route."""
    b = next(iter(arrays.values())).shape[0]
    cap = 1
    while cap < b:
        cap *= 2
    padded = _pad_batch(arrays, cap)
    shape = tuple(padded["num"].shape) + (padded["sb"].shape[-1],)
    with jax.enable_x64():
        fn = _kernel_for(plan, shape)
        mask = fn(*[padded[k] for k in _ARRAY_ORDER])
        out = np.asarray(mask)
    return out[:b]
