# Pallas kernels for the VMEM-resident local phase.
from typing import Optional

import jax

# Scoped VMEM a kernel may ask the compiler for.  A TPU v5e core has
# 128 MiB of VMEM; the compiler accepts a local sort whose single buffer
# takes 64 MiB of it (a 2^24-key chunk), which with headroom for the
# compiler's own scratch sets this limit.  `local_sort.max_chunk` derives
# the chunk bound from it; `repro.analysis` rule R3 enforces it statically
# for every `pallas_call` in a lowered workload.
VMEM_BYTES_PER_CORE = 96 * 1024 * 1024

#: headroom the compiler needs beyond a kernel's own VMEM buffers
VMEM_HEADROOM = 4 * 1024 * 1024


def vmem_bytes(buffer_bytes: int) -> int:
    """Scoped VMEM limit for a kernel whose buffers take `buffer_bytes`."""
    return min(VMEM_BYTES_PER_CORE, buffer_bytes + VMEM_HEADROOM)


# Per-device HBM capacity the compiled programs budget against (a 16 GiB
# accelerator attach point; CPU emulation has host RAM instead but the
# production contract is sized to this).  `repro.analysis` rule R10 gates
# each workload's peak live bytes — from the XLA buffer liveness of the
# compiled module — against it, and the headroom it reports is what sizes
# the KV prefix pools of the serving scheduler.
HBM_BYTES_PER_DEVICE = 16 * 1024 * 1024 * 1024


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in the interpreter.

    Compiled on a TPU, interpreted on every other backend.  An explicit
    value overrides the backend, so a test can lower a kernel for a
    described chip from a CPU-only process.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
