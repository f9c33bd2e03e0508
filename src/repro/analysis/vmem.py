"""Static facts about every `pallas_call` in a traced program.

Two fact extractors share one recursive jaxpr walker (through pjit,
scan/while bodies, cond branches, shard_map, custom-derivative wrappers):

* `pallas_footprints` — rule R3's view: the bytes each call keeps resident
  per grid step (one block per operand/result BlockSpec that the pipeline
  stages in VMEM — operands left in HBM (`pl.ANY`) and moved by the
  kernel's own DMAs count nothing — plus every scratch operand), compared
  against the per-core VMEM ceiling so oversized chunks fail at lowering
  time instead of as a runtime crash at production sizes.
* `pallas_call_facts` — rules R5/R7/R8's view: the full grid, every
  operand's array/block shapes and a *callable* index map (the BlockSpec's
  `index_map_jaxpr` evaluated concretely per grid point), and the kernel
  jaxpr itself — enough to statically replay the block schedule and the
  kernel's predicate structure without executing anything.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Tuple

import numpy as np


@dataclass(frozen=True)
class PallasFootprint:
    name: str                    # kernel name (debug info) or "pallas_call"
    grid: tuple
    block_bytes: int             # sum over in/out BlockSpec blocks
    scratch_bytes: int           # sum over scratch shapes (VMEM/SMEM)
    blocks: tuple                # ((shape, dtype_str), ...) for the message

    @property
    def total_bytes(self) -> int:
        return self.block_bytes + self.scratch_bytes


def _block_numel(block_shape) -> int:
    n = 1
    for d in block_shape:
        if d is None:            # squeezed dim
            continue
        n *= int(getattr(d, "block_size", d))   # plain int or Blocked dim
    return n


def _aval_bytes(aval) -> int:
    shape = tuple(getattr(aval, "shape", ()) or ())
    dtype = np.dtype(getattr(aval, "dtype", np.float32))
    return int(np.prod(shape, dtype=np.int64)) * dtype.itemsize if shape \
        else dtype.itemsize


def _sub_jaxprs(value):
    if hasattr(value, "eqns"):                   # Jaxpr
        yield value
    elif hasattr(value, "jaxpr") and hasattr(value.jaxpr, "eqns"):
        yield value.jaxpr                        # ClosedJaxpr
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def _walk_pallas_calls(jaxpr_like: Any, on_eqn: Callable[[Any], None]) -> None:
    """Call `on_eqn` on every pallas_call eqn reachable from `jaxpr_like`."""
    seen = set()

    def visit(jaxpr):
        if id(jaxpr) in seen:
            return
        seen.add(id(jaxpr))
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                on_eqn(eqn)
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    visit(sub)

    for j in _sub_jaxprs(jaxpr_like):
        visit(j)


def pallas_footprints(jaxpr_like: Any) -> List[PallasFootprint]:
    """All pallas_call footprints reachable from a jaxpr or ClosedJaxpr."""
    out: List[PallasFootprint] = []
    _walk_pallas_calls(jaxpr_like, lambda eqn: out.append(_footprint(eqn)))
    return out


def _in_hbm(bm) -> bool:
    """Operand left in HBM for the kernel's own DMAs (never staged)."""
    space = getattr(bm.transformed_block_aval, "memory_space", None)
    return str(space) in ("any", "hbm")


def _footprint(eqn) -> PallasFootprint:
    gm = eqn.params["grid_mapping"]
    blocks = []
    block_bytes = 0
    for bm in gm.block_mappings:
        dtype = np.dtype(bm.array_aval.dtype)
        if not _in_hbm(bm):
            block_bytes += _block_numel(bm.block_shape) * dtype.itemsize
        blocks.append((tuple(d if d is None else int(getattr(d, "block_size",
                                                             d))
                             for d in bm.block_shape), str(dtype)))
    scratch_bytes = sum(_aval_bytes(a) for a in gm.scratch_avals)
    name = str(eqn.params.get("name", "")) or "pallas_call"
    return PallasFootprint(name=name, grid=tuple(gm.grid),
                           block_bytes=block_bytes,
                           scratch_bytes=scratch_bytes,
                           blocks=tuple(blocks))


# ---------------------------------------------------------------------------
# R5/R7/R8 facts: block schedules and kernel jaxprs
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class OperandFacts:
    """One input or output BlockSpec, with its index map made callable."""
    role: str                        # "in" | "out"
    array_shape: Tuple[int, ...]
    dtype: str
    block_shape: Tuple[Optional[int], ...]   # None = squeezed dim
    index_map: Callable              # grid indices -> block indices

    @property
    def full_block(self) -> Tuple[int, ...]:
        """Block shape with squeezed dims restored as size 1."""
        return tuple(1 if d is None else d for d in self.block_shape)


@dataclass(frozen=True)
class PallasCallFacts:
    name: str
    grid: Tuple[int, ...]
    inputs: Tuple[OperandFacts, ...]
    outputs: Tuple[OperandFacts, ...]
    kernel_jaxpr: Any                # the kernel body (a Jaxpr), or None
    static_grid: bool                # False when any grid bound is dynamic


def _index_map_fn(bm) -> Callable:
    """The BlockSpec's index_map as a concrete python callable."""
    import jax
    cj = bm.index_map_jaxpr

    def index_map(*grid_idx):
        outs = jax.core.eval_jaxpr(cj.jaxpr, cj.consts, *grid_idx)
        return tuple(int(o) for o in outs)

    return index_map


def pallas_call_facts(jaxpr_like: Any) -> List[PallasCallFacts]:
    """Grid/block/kernel facts for every reachable pallas_call."""
    out: List[PallasCallFacts] = []

    def on_eqn(eqn):
        gm = eqn.params["grid_mapping"]
        n_in = int(getattr(gm, "num_inputs", 0))
        grid = tuple(gm.grid)
        static = (getattr(gm, "num_dynamic_grid_bounds", 0) == 0
                  and all(isinstance(g, (int, np.integer)) for g in grid))
        ops: List[OperandFacts] = []
        for k, bm in enumerate(gm.block_mappings):
            sd = bm.array_aval
            block = tuple(
                None if d is None else int(getattr(d, "block_size", d))
                for d in bm.block_shape)
            ops.append(OperandFacts(
                role="in" if k < n_in else "out",
                array_shape=tuple(int(s) for s in sd.shape),
                dtype=str(np.dtype(sd.dtype)),
                block_shape=block,
                index_map=_index_map_fn(bm)))
        name = str(eqn.params.get("name", "")) or "pallas_call"
        out.append(PallasCallFacts(
            name=name, grid=grid,
            inputs=tuple(o for o in ops if o.role == "in"),
            outputs=tuple(o for o in ops if o.role == "out"),
            kernel_jaxpr=eqn.params.get("jaxpr"),
            static_grid=static))

    _walk_pallas_calls(jaxpr_like, on_eqn)
    return out
