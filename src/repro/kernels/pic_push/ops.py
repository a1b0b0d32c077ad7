"""Jitted wrapper: the Pallas kernel on TPU, the jnp oracle elsewhere."""
from __future__ import annotations

from repro.distributed import compat
from repro.kernels import on_tpu
from repro.kernels.pic_push.kernel import pic_push_pallas
from repro.kernels.pic_push.ref import pic_push_ref


def pic_push(grid_q, x, y, vx, vy, q, *, L, dt=1.0, mass=1.0,
             use_kernel: bool = None):
    """Advance particles one step.  Returns (x, y, vx, vy).

    ``use_kernel=None`` auto-selects: native Pallas on TPU; the jnp oracle on
    CPU (interpret mode is Python-slow for large N — the oracle agrees to
    f32 rounding, see tests/test_kernels.py).
    """
    if use_kernel is None:
        use_kernel = on_tpu()
    with compat.named_scope("kernel/pic-push"):
        if use_kernel:
            return pic_push_pallas(grid_q, x, y, vx, vy, q, L=L, dt=dt,
                                   mass=mass, interpret=not on_tpu())
        return pic_push_ref(grid_q, x, y, vx, vy, q, L=L, dt=dt,
                            mass=mass)
