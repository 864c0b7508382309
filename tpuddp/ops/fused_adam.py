"""FusedAdam — a Pallas TPU kernel for the Adam update.

The reference stack's optimizer path bottoms out in torch's fused C++/CUDA
kernels (`torch.optim.Adam(fused=...)` / apex FusedAdam); this is the
TPU-native analog: one Pallas kernel per parameter leaf performs the whole
m/v/p update in a single VMEM pass.

Measured honestly (AlexNet-class, TPU v5 lite): XLA's own elementwise fusion
of the jnp Adam beats this kernel (10.5 vs 15.6 ms/step) — the pad-to-lane
reshape around each leaf costs extra HBM copies that XLA's native fusion never
materializes. The lesson is recorded here deliberately: on TPU, custom kernels
pay off for ops XLA *can't* fuse (attention-style memory patterns, remote
DMA), not for elementwise chains. ``impl="auto"`` therefore resolves to the
XLA path; ``impl="pallas"`` opts into the kernel (compiled for the TPU unless
the caller passes ``interpret=True``, as the CPU tests do), which remains the
framework's example of integrating a custom Pallas op into the training stack
(grid/BlockSpec tiling, SMEM scalars, interpret-mode CPU testing).

Update rule matches tpuddp.optim.Adam (== torch.optim.Adam) exactly:
    m <- b1*m + (1-b1)*g ;  v <- b2*v + (1-b2)*g^2
    p <- p - lr * (m / (1-b1^t)) / (sqrt(v / (1-b2^t)) + eps)
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpuddp.optim import Adam, AdamState

LANES = 128
BLOCK_ROWS = 512  # (512, 128) f32 tiles x 7 live arrays ≈ 1.8 MB of VMEM


def _adam_kernel(bc_ref, p_ref, g_ref, m_ref, v_ref, op_ref, om_ref, ov_ref,
                 *, lr, b1, b2, eps):
    bc1 = bc_ref[0, 0]
    bc2 = bc_ref[0, 1]
    g = g_ref[:]
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * g * g
    om_ref[:] = m
    ov_ref[:] = v
    op_ref[:] = p_ref[:] - lr * (m / bc1) * (1.0 / (jnp.sqrt(v / bc2) + eps))


def _update_leaf(p, g, m, v, bc, *, lr, b1, b2, eps, interpret):
    """Run the kernel over one parameter leaf (any shape/f32)."""
    shape = p.shape
    n = p.size
    rows = max(1, -(-n // LANES))
    rows_padded = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    total = rows_padded * LANES

    def prep(x):
        flat = x.reshape(-1).astype(jnp.float32)
        return jnp.pad(flat, (0, total - n)).reshape(rows_padded, LANES)

    p2, g2, m2, v2 = prep(p), prep(g), prep(m), prep(v)
    grid = (rows_padded // BLOCK_ROWS,)
    tile = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    smem = pl.BlockSpec((1, 2), lambda i: (0, 0), memory_space=pltpu.SMEM)
    out_sds = jax.ShapeDtypeStruct((rows_padded, LANES), jnp.float32)

    op, om, ov = pl.pallas_call(
        functools.partial(_adam_kernel, lr=lr, b1=b1, b2=b2, eps=eps),
        grid=grid,
        in_specs=[smem, tile, tile, tile, tile],
        out_specs=[tile, tile, tile],
        out_shape=[out_sds, out_sds, out_sds],
        interpret=interpret,
    )(bc, p2, g2, m2, v2)

    unpack = lambda x: x.reshape(-1)[:n].reshape(shape)
    return unpack(op).astype(p.dtype), unpack(om), unpack(ov)


def fused_adam_update(params, grads, opt_state: AdamState, *, lr, b1, b2, eps,
                      interpret=False) -> Tuple:
    """Pure-function fused update over a pytree; returns (params, AdamState)."""
    step = opt_state.step + 1
    t = step.astype(jnp.float32)
    bc = jnp.stack([1.0 - jnp.power(b1, t), 1.0 - jnp.power(b2, t)]).reshape(1, 2)

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(opt_state.m)
    flat_v = treedef.flatten_up_to(opt_state.v)
    out_p, out_m, out_v = [], [], []
    for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
        np_, nm, nv = _update_leaf(
            p, g, m, v, bc, lr=lr, b1=b1, b2=b2, eps=eps, interpret=interpret
        )
        out_p.append(np_)
        out_m.append(nm)
        out_v.append(nv)
    unflatten = treedef.unflatten
    return unflatten(out_p), AdamState(step=step, m=unflatten(out_m), v=unflatten(out_v))


class FusedAdam(Adam):
    """Drop-in Adam whose update can run as a Pallas kernel.

    ``impl``: "auto" (XLA math — measured faster, see module docstring),
    "pallas" (force the kernel), or "xla" (inherit tpuddp.optim.Adam
    explicitly). ``interpret``: run the kernel in the Pallas interpreter —
    never guessed from the platform; the caller (a CPU test) says so.
    """

    def __init__(self, lr: float = 1e-3, betas=(0.9, 0.999), eps: float = 1e-8,
                 impl: str = "auto", interpret: bool = False):
        super().__init__(lr=lr, betas=betas, eps=eps, weight_decay=0.0)
        if impl not in ("auto", "pallas", "xla"):
            raise ValueError(f"unknown impl {impl!r}")
        self.impl = impl
        self.interpret = interpret

    def update(self, grads, opt_state, params):
        if self.impl != "pallas":  # auto == xla: measured faster on TPU
            return super().update(grads, opt_state, params)
        return fused_adam_update(
            params, grads, opt_state,
            lr=self.lr, b1=self.b1, b2=self.b2, eps=self.eps,
            interpret=self.interpret,
        )
