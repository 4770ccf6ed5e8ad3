"""Pallas multi-key hash-agg placement kernel (the TPU tier under `hash_groupby`).

The reference formulation in `kernels/relational.py` assigns group ids with a
vectorized round loop: each round, unresolved rows probing an EMPTY slot elect
an owner by scatter-min on row index, then every row verifies its identity
lanes against the owner's.  On TPU that scatter serializes; this kernel walks
the same open-addressing schedule as an explicit in-VMEM loop instead — the
`AggOpenHashMap` insert loop (SURVEY.md §3.3) expressed as a Pallas program.

Exact equivalence to the reference round (proved by the `kernel` marker suite,
bit-for-bit): one round here is two sequential passes over the rows —

- pass 1 (elect): ascending row order, an unresolved row probing a slot that
  was empty AT ROUND START claims it first-write-wins.  First-write-wins in
  ascending order IS scatter-min on row index, and the round-start snapshot
  (`occ`) reproduces the reference's "occupied" read-before-scatter.
- pass 2 (adopt): every unresolved row compares its identity lanes (data AND
  valid) against the slot owner elected above; matches adopt the slot as gid.

A fully sequential insert loop (no round structure) would NOT be equivalent —
a row can win a later-probe slot the reference reserves for a later round —
hence the two-pass round shape.  Rounds past convergence are identity in the
reference (every candidate is sentinel), so running the static `max_rounds`
gated on an unresolved counter matches the reference's early-exit while_loop.

The `pl.pallas_call` is constructed inside a `global_jit` builder (galaxylint
`pallas-raw`): the kernel object is cached per static shape and the call
traces into the enclosing operator program, so zero-steady-retrace discipline
and the overflow ladder (placement failure -> doubled capacity) are unchanged.

TPU note: the probe math is uint64 (bit-identical with `hash_columns`); Mosaic
int64 support is limited on older TPU generations — 32-bit limb emulation of
the `(s0 + r*step) & (M-1)` walk is the known follow-up (the masked stride is
exact in uint32 because M divides 2^32).  Off-TPU backends run interpret mode,
which is what the CPU correctness matrix exercises.
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from galaxysql_tpu.exec import operators as ops
from galaxysql_tpu.runtime import exec_platform


def _interpret() -> bool:
    """Mosaic lowering only where the enclosing program runs on a TPU (a
    program under the TP path's CPU pin does not, whatever the default
    backend); everywhere else the kernel runs in interpret mode
    (reference-exact, slow — gated behind the selector)."""
    return exec_platform() != "tpu"


def _make_place_kernel(n: int, M: int, max_rounds: int,
                       has_valid: Tuple[bool, ...]):
    sentinel = np.int32(n)
    mask = np.uint64(M - 1)
    k = len(has_valid)

    def kernel(*refs):
        live_ref, s0_ref, step_ref = refs[0], refs[1], refs[2]
        d_refs, v_refs = [], []
        pos = 3
        for hv in has_valid:
            d_refs.append(refs[pos])
            pos += 1
            v_refs.append(refs[pos] if hv else None)
            pos += 1 if hv else 0
        rep_ref, resolved_ref, gid_ref, occ_ref, unres_ref = refs[pos:pos + 5]

        rep_ref[...] = jnp.full((M,), sentinel, jnp.int32)
        resolved_ref[...] = jnp.where(live_ref[...],
                                      jnp.int8(0), jnp.int8(1))
        gid_ref[...] = jnp.zeros((n,), jnp.int32)
        unres_ref[0] = jnp.sum(live_ref[...]).astype(jnp.int32)

        def slot_of(i, ru):
            return ((s0_ref[i] + ru * step_ref[i]) & mask).astype(jnp.int32)

        def round_body(r, carry):
            @pl.when(unres_ref[0] > 0)
            def _round():
                ru = r.astype(jnp.uint64)
                # round-start occupancy snapshot: rows probing a slot claimed
                # EARLIER THIS ROUND must still bid (and lose to the smaller
                # row id), exactly like the reference's pre-scatter read
                occ_ref[...] = (rep_ref[...] != sentinel).astype(jnp.int8)

                def elect(i, c):
                    @pl.when(resolved_ref[i] == 0)
                    def _():
                        s = slot_of(i, ru)

                        @pl.when((occ_ref[s] == 0) &
                                 (rep_ref[s] == sentinel))
                        def _():
                            rep_ref[s] = i.astype(jnp.int32)
                    return c

                jax.lax.fori_loop(0, n, elect, 0)

                def adopt(i, c):
                    @pl.when(resolved_ref[i] == 0)
                    def _():
                        s = slot_of(i, ru)
                        owner = rep_ref[s]
                        safe = jnp.clip(owner, 0, max(n - 1, 0))
                        same = owner != sentinel
                        for d_ref, v_ref in zip(d_refs, v_refs):
                            same = same & (d_ref[safe] == d_ref[i])
                            if v_ref is not None:
                                same = same & (v_ref[safe] == v_ref[i])

                        @pl.when(same)
                        def _():
                            resolved_ref[i] = jnp.int8(1)
                            gid_ref[i] = s
                            unres_ref[0] = unres_ref[0] - 1
                    return c

                jax.lax.fori_loop(0, n, adopt, 0)
            return carry

        jax.lax.fori_loop(0, max_rounds, round_body, 0)

    return kernel


def hash_place(ident: Sequence[Tuple[Any, Any]], live: Any, s0: Any,
               step: Any, M: int, max_rounds: int):
    """Slot placement for `hash_groupby`: (rep, resolved, gid), bit-identical
    to the reference scatter-min round loop.  `ident` are the canonicalized
    identity lanes (`_ident_lanes`), `s0`/`step` the masked uint64 probe walk."""
    n = int(live.shape[0])
    has_valid = tuple(v is not None for _, v in ident)
    dts = tuple(str(d.dtype) for d, _ in ident)
    interp = _interpret()
    key = ("pallas_agg_place", n, M, max_rounds, has_valid, dts, interp)

    def build():
        kernel = _make_place_kernel(n, M, max_rounds, has_valid)
        out_shape = (
            jax.ShapeDtypeStruct((M,), jnp.int32),   # rep: slot owner row
            jax.ShapeDtypeStruct((n,), jnp.int8),    # resolved (bool as i8)
            jax.ShapeDtypeStruct((n,), jnp.int32),   # gid
            jax.ShapeDtypeStruct((M,), jnp.int8),    # occ round snapshot
            jax.ShapeDtypeStruct((1,), jnp.int32),   # unresolved counter
        )
        return pl.pallas_call(kernel, out_shape=out_shape, interpret=interp)

    call = ops.global_jit(key, build)
    args = [live, s0, step]
    for (d, v), hv in zip(ident, has_valid):
        args.append(d)
        if hv:
            args.append(v)
    rep, resolved8, gid, _occ, _unres = call(*args)
    return rep, resolved8.astype(jnp.bool_), gid
