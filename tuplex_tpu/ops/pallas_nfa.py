"""Pallas kernel for the dense Glushkov NFA scan.

The dense engine (ops/nfa.py match_dense) advances an [N, P] f32 state
across string byte columns with one [P, P] matmul per column — already
MXU-shaped, but under plain `lax.scan` XLA round-trips the state through
HBM between steps. This kernel blocks rows into tiles and runs the WHOLE
width loop inside one kernel instance, keeping the state, the follow
matrix, and the class table resident in VMEM (the Pallas playbook:
sequential dependence inside the kernel, parallelism across the grid).

The kernel is TRANSPOSED relative to match_dense: rows ride the 128-lane
axis and string positions the sublane axis, so the per-step byte column
is a dynamic SUBLANE slice of an int32 block (`bytes_ref[pl.ds(j, 1), :]`).
The row-major layout needed a dynamic lane slice of a u8 block, which
Mosaic refuses ("cannot statically prove that index in dimension 1 is a
multiple of 128"). State is [Pp, B]; both products are taken from the
left (classT @ onehot, followT @ S).

Selected with TUPLEX_NFA_IMPL=pallas. On CPU the kernel runs in Pallas
interpret mode (slow, for correctness tests); on TPU it compiles to
Mosaic — tests/test_chip_compile.py compiles it for a described v5e.
Position tables pad to sublane multiples (8).
"""

from __future__ import annotations

import functools

import numpy as np

from ..runtime.jaxcfg import jax, jnp

_ROW_BLOCK = 256


@functools.lru_cache(maxsize=32)
def _build_kernel(P: int, w: int, anchored_start: bool, anchored_end: bool,
                  interpret: bool):
    from jax.experimental import pallas as pl

    # pad positions to a SUBLANE multiple (8): padding P to 128 would
    # waste 16x matmul work for small patterns
    Pp = max(8, -(-P // 8) * 8)
    B = _ROW_BLOCK

    def kernel(bytes_ref, lens_ref, end_ref, m0_ref, follow_ref, class_ref,
               first_ref, last_ref, out_ref):
        lens = lens_ref[...]                                  # [1, B]
        end_at = end_ref[...]
        followT = follow_ref[...]                             # [Pp, Pp]
        classT = class_ref[...]                               # [Pp, 256]
        firstv = first_ref[...]                               # [Pp, 1]
        lastv = last_ref[...]
        codes = jax.lax.broadcasted_iota(jnp.int32, (256, B), 0)
        # f32 literals: under x64 a bare 1.0 is f64, and Mosaic has no
        # f64->f32 cast
        one = jnp.float32(1.0)
        zero = jnp.float32(0.0)

        def body(j, carry):
            S, matched = carry
            row = bytes_ref[pl.ds(j, 1), :]                   # [1, B] i32
            # class membership via one-hot matmul, not a ref gather:
            # Mosaic rejects int indexing on VMEM refs, and the
            # [Pp,256]x[256,B] product is MXU work anyway
            onehot = jnp.where(codes == row, one, zero)       # [256, B]
            cm = jnp.dot(classT, onehot,
                         preferred_element_type=jnp.float32)  # [Pp, B]
            nxt = jnp.dot(followT, S,
                          preferred_element_type=jnp.float32) > 0.5
            if anchored_start:
                seed = jnp.where(j == 0, firstv, zero)        # [Pp, 1]
            else:
                seed = firstv
            S2 = jnp.where((nxt | (seed > 0.5)) & (cm > 0.5), one, zero)
            S2 = jnp.where(j < lens, S2, zero)
            hit = jnp.max(S2 * lastv, axis=0, keepdims=True) > 0.5
            if anchored_end:
                hit = hit & ((j + 1 == lens) | (j + 1 == end_at))
            return S2, jnp.where(hit, 1, matched)

        _, matched = jax.lax.fori_loop(
            0, w, body, (jnp.zeros((Pp, B), jnp.float32), m0_ref[...]))
        out_ref[...] = matched

    def run(bytes_t, lens_p, end_p, m0_p, followT, classT, firstv, lastv):
        npad = bytes_t.shape[1]
        rows = pl.BlockSpec((1, B), lambda i: (0, i))
        return pl.pallas_call(
            kernel,
            grid=(npad // B,),
            in_specs=[
                pl.BlockSpec((w, B), lambda i: (0, i)),
                rows, rows, rows,
                pl.BlockSpec((Pp, Pp), lambda i: (0, 0)),
                pl.BlockSpec((Pp, 256), lambda i: (0, 0)),
                pl.BlockSpec((Pp, 1), lambda i: (0, 0)),
                pl.BlockSpec((Pp, 1), lambda i: (0, 0)),
            ],
            out_specs=rows,
            out_shape=jax.ShapeDtypeStruct((1, npad), jnp.int32),
            interpret=interpret,
        )(bytes_t, lens_p, end_p, m0_p, followT, classT, firstv, lastv)

    return run, Pp


def match_pallas(rx, bytes_, lens, interpret=None):
    """Drive the kernel: transpose to [w, N] int32, pad rows to the block
    multiple and positions to sublane width, then slice the matches back.
    `interpret=None` picks automatically (Mosaic on TPU, interpret
    elsewhere); the chip-compile test passes False explicitly to force
    the Mosaic path from a CPU host."""
    n, w = bytes_.shape
    P = rx.n_pos
    if P == 0:          # pure-anchor pattern ('^$'): decided by matched0
        lens64, end_at = rx._end_masks(bytes_, lens, w)
        return rx._matched0(n, end_at)
    if interpret is None:
        # Mosaic is the only native target this kernel is tuned for;
        # every other backend interprets
        interpret = jax.default_backend() != "tpu"
    run, Pp = _build_kernel(P, w, rx.anchored_start, rx.anchored_end,
                            interpret)

    lens64, end_at = rx._end_masks(bytes_, lens, w)
    m0 = rx._matched0(n, end_at)

    npad = -(-max(n, 1) // _ROW_BLOCK) * _ROW_BLOCK

    def row(a):         # [N] -> [1, npad] i32
        return jnp.pad(a.astype(jnp.int32), (0, npad - n))[None, :]

    def table(a, cols):     # numpy [r, c] -> f32 [Pp, cols]
        return jnp.asarray(np.pad(a, ((0, Pp - a.shape[0]),
                                      (0, cols - a.shape[1]))))

    # trace the kernel with x64 OFF: global x64 + pallas_call + the Mosaic
    # TPU lowering recurses without bound in jax 0.9 (RecursionError even
    # at limit 100k). All kernel inputs are explicitly 32-bit, so narrowing
    # the promotion rules changes nothing semantically.
    with jax.enable_x64(False):
        out = run(
            jnp.pad(jnp.transpose(bytes_).astype(jnp.int32),
                    ((0, 0), (0, npad - n))),
            row(lens64), row(end_at), row(m0),
            table(rx._follow_dense.T, Pp),
            table(rx._classtab_dense.T, 256),
            table(rx._first_dense[:, None], 1),
            table(rx._last_dense[:, None], 1),
        )
    return out[0, :n] > 0
