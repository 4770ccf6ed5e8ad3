"""Pallas hash-join kernels: slot hashing (build + probe) and CSR expansion.

Three pieces of the CSR join pipeline (`hash_join_build_slots` ->
`hash_join_probe_csr`) move into Pallas here; the surrounding XLA gather /
verify / segment arithmetic is already TPU-shaped and stays in
`kernels/relational.py`:

- `build_slots`: the chained-hash BUILD kernel — per build row, the full
  `hash_columns` mix (SplitMix64 avalanche per lane, NULL tag, 31x combine)
  masked to `M` slots, with dead rows parked at slot `M` so the CSR
  segment-sum drops them.  Emits exactly the slot vector the reference emits.
- `hash_slots`: the same mix for PROBE rows (no liveness masking — the
  reference handles probe liveness in the count step).
- `expand_offsets`: the probe-side pair expansion — the reference's
  scatter-max-at-segment-starts followed by a cummax becomes an explicit
  in-VMEM scatter loop plus a running-max sweep.  Equivalence: first-write at
  each segment start with `jnp.maximum` IS `.at[].max`, the `(count>0) &
  (start<cap)` guard IS `mode="drop"` with the count-0 rows parked at `cap`,
  and the sweep IS `lax.cummax`.

All `pl.pallas_call`s are constructed inside `global_jit` builders (galaxylint
`pallas-raw`) and trace into the enclosing operator program: retrace keys,
the probe-capacity overflow ladder, and hybrid hot/cold splitting are
untouched.  Off-TPU these run in interpret mode (bit-exact; the CPU `kernel`
matrix drives them with `KERNEL(PALLAS)`), and uint64 in-kernel math shares
the Mosaic caveat noted in `pallas_agg` for older TPU generations.
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from galaxysql_tpu.exec import operators as ops
from galaxysql_tpu.kernels.relational import _GOLDEN, _M1, _M2
from galaxysql_tpu.runtime import exec_platform

_NULL_TAG = np.uint64(0xDEADBEEFCAFEBABE)
_THIRTYONE = np.uint64(31)


def _interpret() -> bool:
    """Same rule as `pallas_agg._interpret`: Mosaic only where the program
    runs on a TPU."""
    return exec_platform() != "tpu"


def _mix64_v(h):
    """SplitMix64 avalanche, vectorized over a whole lane inside the kernel —
    same constants, same shift schedule as `relational._mix64`."""
    h = h ^ (h >> np.uint64(33))
    h = h * _M1
    h = h ^ (h >> np.uint64(33))
    h = h * _M2
    h = h ^ (h >> np.uint64(33))
    return h


def _make_slots_kernel(M: int, has_valid: Tuple[bool, ...], masked: bool):
    """Combined-hash slot kernel.  `masked`: build variant — takes a leading
    live lane and parks dead rows at slot M (the CSR drop segment)."""
    mask = np.uint64(M - 1)

    def kernel(*refs):
        pos = 0
        live_ref = None
        if masked:
            live_ref = refs[pos]
            pos += 1
        d_refs, v_refs = [], []
        for hv in has_valid:
            d_refs.append(refs[pos])
            pos += 1
            v_refs.append(refs[pos] if hv else None)
            pos += 1 if hv else 0
        out_ref = refs[pos]

        h = None
        for d_ref, v_ref in zip(d_refs, v_refs):
            lane = _mix64_v(d_ref[...].astype(jnp.uint64))
            if v_ref is not None:
                lane = jnp.where(v_ref[...], lane, _NULL_TAG)
            if h is None:
                h = lane
            else:
                h = _mix64_v(h * _THIRTYONE + lane + _GOLDEN)
        s = (h & mask).astype(jnp.int32)
        if masked:
            s = jnp.where(live_ref[...], s, jnp.int32(M))
        out_ref[...] = s

    return kernel


def _slots_call(keys: Sequence[Tuple[Any, Any]], live, M: int, tag: str):
    n = int(keys[0][0].shape[0])
    has_valid = tuple(v is not None for _, v in keys)
    dts = tuple(str(d.dtype) for d, _ in keys)
    masked = live is not None
    interp = _interpret()
    key = ("pallas_join_slots", tag, n, M, has_valid, dts, masked, interp)

    def build():
        kernel = _make_slots_kernel(M, has_valid, masked)
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
            interpret=interp,
        )

    call = ops.global_jit(key, build)
    args = []
    if masked:
        args.append(live)
    for (d, v), hv in zip(keys, has_valid):
        args.append(d)
        if hv:
            args.append(v)
    return call(*args)


def build_slots(build_keys: Sequence[Tuple[Any, Any]], b_live, M: int):
    """Build-side slot vector: `(hash_columns(keys) & (M-1)) | dead->M`,
    bit-identical with the reference `hash_join_build_slots` body."""
    return _slots_call(build_keys, b_live, M, "build")


def hash_slots(probe_keys: Sequence[Tuple[Any, Any]], M: int):
    """Probe-side slot vector (unmasked): `hash_columns(keys) & (M-1)`."""
    return _slots_call(probe_keys, None, M, "probe")


def _make_expand_kernel(npr: int, cap: int):
    def kernel(counts_ref, starts_ref, p_of_ref):
        p_of_ref[...] = jnp.zeros((cap,), jnp.int32)

        def scat(i, c):
            # (count>0) & (start<cap) reproduces the reference's
            # `.at[scatter_at].max(..., mode="drop")`: count-0 rows are
            # parked at cap there, and overflow starts land out of bounds
            @pl.when((counts_ref[i] > 0) & (starts_ref[i] < cap))
            def _():
                s = starts_ref[i]
                prev = p_of_ref[s]
                p_of_ref[s] = jnp.maximum(prev, i.astype(jnp.int32))
            return c

        jax.lax.fori_loop(0, npr, scat, 0)

        def sweep(j, run):
            run = jnp.maximum(run, p_of_ref[j])
            p_of_ref[j] = run
            return run

        jax.lax.fori_loop(0, cap, sweep, jnp.int32(0))

    return kernel


def expand_offsets(counts, starts, cap: int):
    """Probe->pair owner map: for pair slot j, the probe row whose [start,
    start+count) segment covers j.  Matches the reference scatter-max +
    `lax.cummax` expansion bit-for-bit."""
    npr = int(counts.shape[0])
    interp = _interpret()
    key = ("pallas_join_expand", npr, cap, interp)

    def build():
        kernel = _make_expand_kernel(npr, cap)
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((cap,), jnp.int32),
            interpret=interp,
        )

    call = ops.global_jit(key, build)
    return call(counts, starts)
