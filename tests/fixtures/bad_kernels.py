"""Deliberately-broken Pallas kernels: one per analyzer rule.

Each wrapper below violates exactly ONE of R1-R5 (and nothing else), so
``tests/test_check.py`` can assert the rule engine fires precisely its
intended finding per fixture. These kernels are only ever abstract-traced
(``repro.check.facts.trace_kernel``) — they never run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _misaligned_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[:100, :100] * 2.0


def _waived_misaligned_kernel(x_ref, o_ref):
    # check: waive[R1] — the one waiver in the repo, kept to test waivers.
    o_ref[...] = x_ref[:100, :100] * 2.0


def bad_tile(x, kernel=_misaligned_kernel):
    """R1: (100, 100) output blocks — neither lane (128) nor sublane (8 for
    f32) aligned, and not covering the full array dim. The input stays a
    full-array (aligned-by-exemption) block so only the output trips."""
    return pl.pallas_call(
        kernel,
        grid=(3, 3),
        in_specs=[pl.BlockSpec((256, 256), lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((100, 100), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((256, 256), jnp.float32),
    )(x)


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def bad_index_map(x):
    """R2: the output index_map places block (i+1, j) — grid step i=1 lands
    outside cdiv(256, 128) = 2 blocks (and block row 0 is never written,
    but OOB placements suppress the coverage check so exactly one finding
    fires)."""
    return pl.pallas_call(
        _copy_kernel,
        grid=(2, 2),
        in_specs=[pl.BlockSpec((128, 128), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((128, 128), lambda i, j: (i + 1, j)),
        out_shape=jax.ShapeDtypeStruct((256, 256), jnp.float32),
    )(x)


def _unguarded_kernel(x_ref, o_ref):
    # Race: this store runs on EVERY grid step, but the output block only
    # changes with the outer axis — the revisited block needs the guarded
    # init/accumulate idiom (pl.when + scratch).
    o_ref[...] = x_ref[...] * 2.0


def bad_write_hazard(x):
    """R3: output block (t, 0) is revisited across all 4 inner grid steps
    with an unguarded store on each."""
    return pl.pallas_call(
        _unguarded_kernel,
        grid=(2, 4),
        in_specs=[pl.BlockSpec((128, 128), lambda t, f: (t, f))],
        out_specs=pl.BlockSpec((128, 128), lambda t, f: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((256, 128), jnp.float32),
    )(x)


def _bf16_dot_kernel(x_ref, w_ref, o_ref):
    o_ref[...] = jnp.dot(x_ref[...], w_ref[...])


def bad_accumulator(x, w):
    """R4: a bf16 x bf16 matmul with no preferred_element_type accumulates
    in bf16. Full-array blocks and a single grid step keep R1/R3 quiet."""
    return pl.pallas_call(
        _bf16_dot_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((128, 256), lambda i: (0, 0)),
            pl.BlockSpec((256, 128), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((128, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((128, 128), jnp.bfloat16),
    )(x, w)


def _big_scratch_kernel(x_ref, o_ref, scr):
    scr[:256, :256] = x_ref[...]
    o_ref[...] = scr[:256, :256]


def bad_footprint(x):
    """R5: a (8192, 8192) f32 VMEM scratch is 256MB — 16x the scoped VMEM
    budget on its own."""
    return pl.pallas_call(
        _big_scratch_kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((256, 256), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((256, 256), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((256, 256), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8192, 8192), jnp.float32)],
    )(x)


def waived_tile(x):
    """bad_tile's R1 finding, waived by a comment in the kernel body."""
    return bad_tile(x, kernel=_waived_misaligned_kernel)


# rule -> (wrapper, input ShapeDtypeStructs)
FIXTURES = {
    "R1": (bad_tile,
           (jax.ShapeDtypeStruct((256, 256), jnp.float32),)),
    "R2": (bad_index_map,
           (jax.ShapeDtypeStruct((256, 256), jnp.float32),)),
    "R3": (bad_write_hazard,
           (jax.ShapeDtypeStruct((256, 512), jnp.float32),)),
    "R4": (bad_accumulator,
           (jax.ShapeDtypeStruct((128, 256), jnp.bfloat16),
            jax.ShapeDtypeStruct((256, 128), jnp.bfloat16))),
    "R5": (bad_footprint,
           (jax.ShapeDtypeStruct((256, 256), jnp.float32),)),
}
