"""KernelFacts: a declarative IR for Pallas kernels, extracted statically.

``trace_kernel`` abstract-evaluates a kernel wrapper over
``jax.ShapeDtypeStruct`` inputs (``jax.make_jaxpr`` — nothing executes, no
TPU required), finds every ``pallas_call`` equation, and records what the
analytic model and the rule engine need:

- the grid and its iteration order (last axis innermost, TPU semantics),
- every operand's BlockSpec: block shape, memory space, dtype, and the
  index_map *evaluated over the whole grid* (index maps are pure integer
  arithmetic, so the full block-visit table is computable at trace time),
- scratch shapes/spaces,
- every ``dot_general`` in the kernel body (dtypes, accumulator type,
  flops) and whether each store is guarded by ``pl.when`` (a ``cond``).

The visit tables drive R2/R3 and compile directly to touch streams in
``repro.check.streams``.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass
from functools import partial

import numpy as np

# Grids larger than this would make visit tables (and touch streams)
# unreasonably large for a static pass; the catalog stays well below.
MAX_GRID_STEPS = 1 << 18


def _dtype_name(dt) -> str:
    return np.dtype(dt).name if not hasattr(dt, "name") else dt.name


@dataclass(frozen=True)
class BlockFacts:
    """One pallas_call operand (input or output) and its block placement."""

    role: str                   # "in" | "out"
    index: int                  # position within its role
    name: str                   # kernel-ref name when recoverable, else in<i>
    array_shape: tuple[int, ...]
    dtype: str                  # numpy-style dtype name ("bfloat16", ...)
    block_shape: tuple[int, ...]
    memory_space: str           # "vmem" | "smem" | "any"
    # (n_steps, ndim) int64: index_map output for every grid step, in grid
    # iteration order (last grid axis fastest).
    block_indices: np.ndarray
    # Store counts into this ref from the kernel body (outputs only; inputs
    # keep zeros). "guarded" means inside a pl.when (cond) branch.
    unguarded_stores: int = 0
    guarded_stores: int = 0

    @property
    def itemsize(self) -> int:
        return int(np.dtype(self.dtype).itemsize)

    @property
    def block_bytes(self) -> int:
        return int(math.prod(self.block_shape)) * self.itemsize

    @property
    def array_bytes(self) -> int:
        return int(math.prod(self.array_shape)) * self.itemsize

    @property
    def nblocks(self) -> tuple[int, ...]:
        return tuple(-(-a // b) for a, b in
                     zip(self.array_shape, self.block_shape))

    def fetch_mask(self) -> np.ndarray:
        """True at grid steps where this operand's block differs from the
        previous step's — i.e. where the Pallas pipeline issues a DMA."""
        idx = self.block_indices
        mask = np.ones(len(idx), dtype=bool)
        if len(idx) > 1:
            mask[1:] = np.any(idx[1:] != idx[:-1], axis=1)
        return mask

    def flat_block_ids(self) -> np.ndarray:
        """Row-major flat id of the visited block at each grid step."""
        nb = np.asarray(self.nblocks, dtype=np.int64)
        strides = np.ones_like(nb)
        if len(nb) > 1:
            strides[:-1] = np.cumprod(nb[::-1])[::-1][1:]
        clipped = np.clip(self.block_indices, 0, nb - 1)
        return (clipped * strides).sum(axis=1)

    def runs(self) -> list[tuple[int, int, int]]:
        """Consecutive same-block runs as (flat_block_id, start, stop)."""
        ids = self.flat_block_ids()
        if not len(ids):
            return []
        cuts = np.flatnonzero(self.fetch_mask())
        bounds = np.append(cuts, len(ids))
        return [(int(ids[s]), int(s), int(e))
                for s, e in zip(bounds[:-1], bounds[1:])]


@dataclass(frozen=True)
class ScratchFacts:
    shape: tuple[int, ...]
    dtype: str
    memory_space: str           # "vmem" | "smem"

    @property
    def nbytes(self) -> int:
        return int(math.prod(self.shape)) * int(np.dtype(self.dtype).itemsize)


@dataclass(frozen=True)
class DotFacts:
    """One dot_general in the kernel body."""

    lhs_dtype: str
    rhs_dtype: str
    out_dtype: str
    preferred_element_type: str | None
    out_shape: tuple[int, ...]
    contracted: tuple[int, ...]   # sizes of the contracted lhs dims
    guarded: bool                 # inside a pl.when branch

    @property
    def flops(self) -> float:
        return 2.0 * math.prod(self.out_shape) * math.prod(self.contracted)


@dataclass(frozen=True)
class KernelFacts:
    """Everything the rules and the stream compiler need about one
    pallas_call, anchored at the kernel function's def site."""

    kernel: str                 # kernel function name
    case: str                   # catalog case label (shape-matrix point)
    src_file: str
    src_line: int
    grid: tuple[int, ...]
    inputs: tuple[BlockFacts, ...]
    outputs: tuple[BlockFacts, ...]
    scratch: tuple[ScratchFacts, ...]
    dots: tuple[DotFacts, ...]

    @property
    def n_steps(self) -> int:
        return int(math.prod(self.grid))

    @property
    def blocks(self) -> tuple[BlockFacts, ...]:
        return self.inputs + self.outputs

    def flops_per_step(self) -> float:
        """Flops of the unconditional dots executed every grid step."""
        return sum(d.flops for d in self.dots if not d.guarded)

    def guarded_flops(self) -> float:
        return sum(d.flops for d in self.dots if d.guarded)


# --- jaxpr walking -----------------------------------------------------------

def _sub_closed_jaxprs(eqn):
    """(closed_jaxpr, eqn_invars_for_its_invars, enters_cond) children."""
    out = []
    params = eqn.params or {}
    if eqn.primitive.name == "cond":
        for br in params.get("branches", ()):
            out.append((br, list(eqn.invars[1:]), True))
        return out
    for key in ("jaxpr", "call_jaxpr"):
        sub = params.get(key)
        if sub is not None and hasattr(sub, "jaxpr"):
            out.append((sub, list(eqn.invars), False))
        elif sub is not None and hasattr(sub, "eqns"):
            class _Closed:  # open jaxpr: wrap for a uniform interface
                def __init__(self, j):
                    self.jaxpr, self.consts = j, []
            out.append((_Closed(sub), list(eqn.invars), False))
    return out


def _find_pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        else:
            for closed, _, _ in _sub_closed_jaxprs(eqn):
                yield from _find_pallas_eqns(closed.jaxpr)


def _eval_index_map(closed_jaxpr, grid: tuple[int, ...], ndim: int) -> np.ndarray:
    """Evaluate an index_map jaxpr over every grid step.

    Returns (n_steps, ndim) int64 in grid iteration order (last axis
    fastest — C-order flatten of the meshgrid matches TPU semantics).
    """
    import jax
    import jax.numpy as jnp
    from jax import core as jax_core

    n_steps = int(math.prod(grid))
    if n_steps > MAX_GRID_STEPS:
        raise ValueError(f"grid {grid} has {n_steps} steps "
                         f"(> {MAX_GRID_STEPS}); shrink the catalog case")
    mesh = np.meshgrid(*[np.arange(g, dtype=np.int64) for g in grid],
                       indexing="ij")
    steps = np.stack(mesh, axis=-1).reshape(-1, len(grid))

    def run(*idx):
        return jax_core.eval_jaxpr(closed_jaxpr.jaxpr, closed_jaxpr.consts,
                                   *idx)

    outs = jax.vmap(run)(*[jnp.asarray(steps[:, d], dtype=jnp.int32)
                           for d in range(len(grid))])
    cols = [np.asarray(o, dtype=np.int64).reshape(n_steps) for o in outs]
    if len(cols) != ndim:          # degenerate (rank-0 full-array) mapping
        cols = cols[:ndim] + [np.zeros(n_steps, np.int64)] * (ndim - len(cols))
    return np.stack(cols, axis=1) if cols else np.zeros((n_steps, 0), np.int64)


def _is_var(v) -> bool:
    return hasattr(v, "aval") and not hasattr(v, "val")


def _ref_stores(kjaxpr, ref_vars) -> dict:
    """Count guarded/unguarded stores per kernel ref var (recursively)."""
    counts = {v: [0, 0] for v in ref_vars}   # var -> [unguarded, guarded]

    def walk(jaxpr, mapping, in_cond):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name in ("swap", "addupdate", "masked_swap"):
                tgt = mapping.get(eqn.invars[0]) if _is_var(eqn.invars[0]) \
                    else None
                if tgt is not None:
                    counts[tgt][1 if in_cond else 0] += 1
            for closed, invars, is_cond in _sub_closed_jaxprs(eqn):
                sub = closed.jaxpr
                m2 = {bv: mapping[ov]
                      for bv, ov in zip(sub.invars, invars)
                      if _is_var(ov) and ov in mapping}
                if m2:
                    walk(sub, m2, in_cond or is_cond)

    walk(kjaxpr, {v: v for v in ref_vars}, False)
    return counts


def _collect_dots(kjaxpr) -> list[DotFacts]:
    dots = []

    def walk(jaxpr, in_cond):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
                out = eqn.outvars[0].aval
                (lc, _), _ = eqn.params["dimension_numbers"]
                pref = eqn.params.get("preferred_element_type")
                dots.append(DotFacts(
                    lhs_dtype=_dtype_name(lhs.dtype),
                    rhs_dtype=_dtype_name(rhs.dtype),
                    out_dtype=_dtype_name(out.dtype),
                    preferred_element_type=(
                        _dtype_name(pref) if pref is not None else None),
                    out_shape=tuple(out.shape),
                    contracted=tuple(lhs.shape[d] for d in lc),
                    guarded=in_cond,
                ))
            for closed, _, is_cond in _sub_closed_jaxprs(eqn):
                walk(closed.jaxpr, in_cond or is_cond)

    walk(kjaxpr, False)
    return dots


_SRC_RE = re.compile(r"(\S+\.py):(\d+)")


def _src_of(debug_info) -> tuple[str, str, int]:
    """(kernel name, file, line) from the kernel jaxpr's debug info, whose
    ``func_src_info`` reads ``"<name> at <file>.py:<line>"``."""
    text = getattr(debug_info, "func_src_info", None) or ""
    name = getattr(debug_info, "func_name", None) or "<unknown>"
    m = _SRC_RE.search(text)
    if m:
        return name, m.group(1), int(m.group(2))
    return name, "<unknown>", 0


def _block_dim(dim) -> int:
    """Block extent of one BlockMapping dim: ``Blocked(n)`` -> n; squeezed
    (``None``-sized) dims -> 1."""
    size = getattr(dim, "block_size", dim)
    return int(size) if isinstance(size, (int, np.integer)) else 1


def _memory_space_name(block_aval) -> str:
    space = getattr(block_aval, "memory_space", None)
    if space is None:
        return "vmem"
    s = str(space).lower()
    if "smem" in s:
        return "smem"
    if "any" in s:
        return "any"
    return "vmem"


def _facts_from_eqn(eqn, case: str) -> KernelFacts:
    gm = eqn.params["grid_mapping"]
    kernel_jaxpr = eqn.params["jaxpr"]
    name, src_file, src_line = _src_of(kernel_jaxpr.debug_info)
    grid = tuple(int(g) for g in gm.grid)

    n_index = int(getattr(gm, "num_index_operands", 0))
    n_in = int(gm.num_inputs)
    n_out = int(gm.num_outputs)
    # kernel invars: [index operands..., inputs..., outputs..., scratch...]
    invars = list(kernel_jaxpr.invars)
    in_vars = invars[n_index:n_index + n_in]
    out_vars = invars[n_index + n_in:n_index + n_in + n_out]
    scratch_vars = invars[n_index + n_in + n_out:]

    stores = _ref_stores(kernel_jaxpr, out_vars)
    mappings = list(gm.block_mappings)

    def block_facts(bm, role, i, var) -> BlockFacts:
        sds = bm.array_aval
        block_shape = tuple(_block_dim(b) for b in bm.block_shape)
        unguarded, guarded = stores.get(var, (0, 0)) if role == "out" \
            else (0, 0)
        return BlockFacts(
            role=role, index=i,
            name=f"{role}{i}",
            array_shape=tuple(int(s) for s in sds.shape),
            dtype=_dtype_name(sds.dtype),
            block_shape=block_shape,
            memory_space=_memory_space_name(bm.transformed_block_aval),
            block_indices=_eval_index_map(
                bm.index_map_jaxpr, grid, len(block_shape)),
            unguarded_stores=int(unguarded),
            guarded_stores=int(guarded),
        )

    inputs = tuple(block_facts(mappings[i], "in", i, in_vars[i])
                   for i in range(n_in))
    outputs = tuple(block_facts(mappings[n_in + i], "out", i, out_vars[i])
                    for i in range(n_out))
    scratch = tuple(
        ScratchFacts(
            shape=tuple(int(s) for s in v.aval.shape),
            dtype=_dtype_name(v.aval.dtype),
            memory_space=_memory_space_name(v.aval))
        for v in scratch_vars)

    return KernelFacts(
        kernel=name, case=case, src_file=src_file, src_line=src_line,
        grid=grid, inputs=inputs, outputs=outputs, scratch=scratch,
        dots=tuple(_collect_dots(kernel_jaxpr)),
    )


def trace_kernel(fn, *avals, case: str = "", **kwargs) -> list[KernelFacts]:
    """Abstract-eval ``fn(*avals)`` (ShapeDtypeStructs) and return one
    KernelFacts per pallas_call found, in program order. Nothing executes."""
    import jax

    wrapped = partial(fn, **kwargs) if kwargs else fn
    jaxpr = jax.make_jaxpr(wrapped)(*avals)
    facts = [_facts_from_eqn(eqn, case or getattr(fn, "__name__", "kernel"))
             for eqn in _find_pallas_eqns(jaxpr.jaxpr)]
    if not facts:
        raise ValueError(f"no pallas_call found tracing {fn!r}")
    return facts
