"""Gradient-communication hooks — the tpuddp rebuild of torch DDP's bucketed
allreduce + comm-hook machinery (SURVEY.md §2b: DDP's ``bf16_compress_hook``
et al., the one reference capability tpuddp had not reimplemented natively).

torch DDP flattens gradients into size-capped buckets and lets a registered
comm hook transform each bucket's allreduce (``default_hooks.bf16_compress_hook``
casts the bucket to bf16, allreduces half the bytes, and decompresses).
tpuddp expresses the same pipeline *inside the compiled step*:

1. the gradient pytree is flattened into ONE padded f32 vector with the
   existing :class:`~tpuddp.training.step.FlatParamSpec` vectorizer;
2. the vector is split into size-capped contiguous **buckets**
   (``bucket_cap_mb``, torch's knob/default): whole leaves are packed
   greedily in deterministic ``tree_flatten`` order, so many small tensors
   coalesce into one collective instead of paying per-tensor latency, while
   an oversized leaf gets a bucket of its own;
3. each bucket runs the configured **hook**:

   - ``"none"``  — today's full-precision ``lax.pmean`` (the default; the
     bucketed flat path is bypassed entirely, zero behavior change);
   - ``"bf16"``  — cast the bucket to bf16, ``lax.psum`` it (HALF the
     interconnect bytes), decompress to f32, divide by world;
   - ``"bf16_ef"`` — ``bf16`` plus **error feedback**: each replica keeps a
     persistent local residual of what compression discarded and adds it
     back into the next step's send, so quantization error accumulates into
     later updates instead of biasing the trajectory (1-bit-Adam/DynamiQ
     lineage; arxiv.org/abs/2602.08923). The residual is carried in
     ``TrainState.comm_state`` and checkpoints with the rest of the state;
   - ``"int8_ef"`` — per-bucket max-abs symmetric **int8** quantization
     (~75% fewer wire bytes): int8 codes + one f32 scale per bucket are
     all-gathered and dequant-summed locally (per-replica scales make a
     direct psum meaningless — torch's ``quantization_pertensor_hook``
     takes the same shape), with bf16_ef's error-feedback residual;
   - ``"topk_ef"`` — per-bucket **top-k by magnitude** (``topk_density``,
     default 0.1 => ~87.5% fewer wire bytes): int8-quantized values + int32
     indices + the bucket scale on the wire; the unsent complement AND the
     quantization error fold into the same residual.

Topology (``comm_topology``): ``"flat"`` runs one collective over the whole
data axis; ``"hierarchical"`` (:meth:`GradComm.reduce_hierarchical`, over
the factored ``("host", "local")`` mesh — mesh.hierarchical_mesh) runs
intra-host f32 reduce-scatter, a compressed inter-host exchange of each
1/L shard, then all-gather — only the compressed shard crosses the slow
inter-host link, and :func:`comm_bytes_breakdown` accounts the two hops
separately.

Under ``weight_update_sharding`` the compressed payload is **reduce-
scattered** instead: the bf16 vector is ``psum_scatter``'d whole (the scatter
hands every replica a contiguous 1/N shard aligned with its optimizer-moment
shard, so the bucket partition would scramble shard ownership — buckets
degenerate to the full vector there and remain an accounting construct).
Gradient wire bytes still halve; the f32 parameter all-gather is unchanged.

Modes and honesty:

- ``mode="shard_map"`` (explicit): the emitted program requests the
  collective in the wire dtype — the lowered step carries a bf16
  all-reduce/reduce-scatter (asserted in tests/test_comm.py; TPU ICI runs
  bf16 collectives natively, while backends without them — the CPU test
  world — legalize to f32 at compile time, preserving the quantization
  numerics). :func:`comm_bytes_for_hook` is the measured-artifact counter
  for the reduction.
- ``mode="auto"`` / the managed Accelerator: XLA inserts the cross-replica
  psum inside backward where a dtype cast cannot be interposed, so the hook
  quantizes the *aggregated* gradient with the same error-feedback residual
  — the convergence contract (what the numerics tests pin) is preserved,
  but the byte reduction is a property of the explicit path only, and the
  counter accounts for it honestly (``comm_bytes_for_hook(wire=False)``
  reports the f32 payload those paths actually reduce).
  :func:`local_quantize` is that tree-level emulation.

Per-replica residual layout (shard_map): a flat ``(world * total,)`` f32
vector sharded ``P("data")`` over the mesh — inside ``shard_map`` each
replica sees its own ``(total,)`` slice, exactly like the weight-update-
sharded optimizer moments. Checkpointing gathers it cross-host like any
other sharded leaf (training/checkpoint.py).

Numerical-guard composition (``training.guard``, resilience/guard.py): the
non-finite firewall checks the f32 gradient payload — post-allreduce on the
explicit path (bf16 keeps f32's exponent range, so quantization cannot mask
a non-finite payload from the decompressed check), pre-quantization on the
auto/managed path where the aggregate already exists — and a skipped step
hands back the PRE-step residual, so a poisoned ``send`` (gradient +
residual) never contaminates the error-feedback state (training/step.py's
``gate``).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

COMM_HOOKS = ("none", "bf16", "bf16_ef", "int8_ef", "topk_ef")

# Hooks that carry the persistent error-feedback residual in
# TrainState.comm_state (the DynamiQ lineage, arxiv.org/abs/2602.08923):
# whatever a step's compression dropped — quantization rounding for
# bf16_ef/int8_ef, the whole unsent complement for topk_ef — re-enters the
# next step's send, so compression error accumulates into later updates
# instead of biasing the trajectory.
EF_HOOKS = ("bf16_ef", "int8_ef", "topk_ef")

# torch DDP's bucket_cap_mb default. Small enough that many buckets exist on
# real models (XLA can pipeline the collectives), large enough that small
# tensors coalesce instead of paying per-tensor collective latency.
DEFAULT_BUCKET_CAP_MB = 25

# topk_ef's density knob default: keep the top 10% of each bucket by
# magnitude (values int8-quantized + int32 indices + one f32 scale per
# bucket => ~87.5% fewer gradient wire bytes than f32 at this density).
DEFAULT_TOPK_DENSITY = 0.1

_WIRE_DTYPES = {"bf16": jnp.bfloat16, "bf16_ef": jnp.bfloat16}
_F32_BYTES = 4
_INT8_BYTES = 1
_IDX_BYTES = 4  # top-k indices travel as int32
_SCALE_BYTES = 4  # one f32 max-abs scale per bucket rides the wire

COMM_TOPOLOGIES = ("flat", "hierarchical")


def wire_dtype(hook: str):
    """The on-the-wire dtype of a hook's gradient collective (f32 for none).
    Only meaningful for the dense cast hooks (bf16/bf16_ef); the int8/top-k
    hooks carry a structured payload (int8 values [+ int32 indices] + f32
    scales) whose bytes :func:`comm_bytes_for_hook` accounts per part."""
    return _WIRE_DTYPES.get(hook, jnp.float32)


def wire_itemsize(hook: str) -> int:
    return jnp.dtype(wire_dtype(hook)).itemsize


def validate_hook(hook: str) -> str:
    if hook not in COMM_HOOKS:
        raise ValueError(f"unknown comm_hook {hook!r}; one of {COMM_HOOKS}")
    return hook


def validate_topology(topology: str) -> str:
    if topology not in COMM_TOPOLOGIES:
        raise ValueError(
            f"unknown comm_topology {topology!r}; one of {COMM_TOPOLOGIES}"
        )
    return topology


def loss_parity_tol(hook: str, base_loss: float) -> float:
    """The documented loss-trajectory parity bound of each hook vs the
    uncompressed run — what the dryrun, the full gate's compression-matrix
    leg, and the bench assert. Dense hooks (bf16*/int8_ef) track the f32
    trajectory step for step: ``max(0.05, 0.02 |base|)`` (the bf16_ef bound
    PR 2 shipped). ``topk_ef`` provably converges to the same optimum but
    with an error-feedback WARMUP LAG of O(1/density) steps (until every
    coordinate has been sent at least once, ~90% of the gradient rides the
    residual at density 0.1), so short-horizon comparisons get the looser
    ``max(0.35, 0.25 |base|)``; past the warmup (>= ~2/density updates) the
    trajectories re-converge and the dense bound empirically holds again
    (tests/test_comm.py pins both regimes)."""
    validate_hook(hook)
    if hook == "topk_ef":
        return max(0.35, 0.25 * abs(base_loss))
    return max(0.05, 0.02 * abs(base_loss))


def bucket_topk(size: int, density: float) -> int:
    """Elements topk_ef keeps of a ``size``-element bucket: ``density`` of it,
    floored, never below 1 (an empty send would stall the layer forever)."""
    if not (0.0 < density <= 1.0):
        raise ValueError(f"topk density must be in (0, 1], got {density!r}")
    return max(1, int(size * density))


# ------------------------------------------------- int8 / top-k primitives --


def quantize_int8(b, scale):
    """Symmetric max-abs int8 quantization of a bucket against ``scale``
    (= max|b| / 127). The divide guards the all-zero bucket (scale 0 -> send
    zeros); a NON-FINITE scale (any NaN/Inf in the bucket) is deliberately
    NOT guarded — dequantization multiplies by the raw scale, so a poisoned
    bucket decompresses to NaN everywhere and the numerical-guard firewall
    sees it (int8's range, unlike bf16's exponent-preserving cast, could
    otherwise mask a non-finite payload)."""
    denom = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(b / denom), -127, 127).astype(jnp.int8)


def int8_scale(b):
    """Per-bucket max-abs scale (f32 scalar); NaN/Inf in the bucket poisons
    it, which is the guard-visibility contract (see quantize_int8)."""
    return (jnp.max(jnp.abs(b)) / 127.0).astype(jnp.float32)


def make_buckets(
    sizes: Tuple[int, ...], total: int, bucket_cap_mb: float = DEFAULT_BUCKET_CAP_MB
) -> Tuple[Tuple[int, int], ...]:
    """Partition ``[0, total)`` into contiguous ``(start, end)`` buckets.

    ``sizes`` are the flat-vector leaf sizes in ``tree_flatten`` order (the
    deterministic order :func:`~tpuddp.training.step._tree_to_vec`
    concatenates in), so bucket boundaries land on whole-leaf boundaries:
    leaves are packed greedily until the next leaf would push the bucket past
    ``bucket_cap_mb`` of f32 payload; a single leaf larger than the cap gets
    its own bucket (torch DDP's rule — tensors are never split). The final
    bucket absorbs the spec's world-multiple zero padding (``total`` minus
    the raw leaf sum), so the buckets always cover the padded vector exactly.
    """
    if bucket_cap_mb <= 0:
        raise ValueError(f"bucket_cap_mb must be > 0, got {bucket_cap_mb!r}")
    cap_elems = max(1, int(bucket_cap_mb * 1024 * 1024) // _F32_BYTES)
    buckets = []
    start = 0
    cursor = 0
    filled = 0
    for size in sizes:
        if filled and filled + size > cap_elems:
            buckets.append((start, cursor))
            start, filled = cursor, 0
        cursor += size
        filled += size
    # the tail bucket: remaining leaves plus the zero padding up to `total`
    if cursor < total or filled or start < total:
        buckets.append((start, total))
    assert buckets and buckets[0][0] == 0 and buckets[-1][1] == total
    return tuple(buckets)


class GradComm(NamedTuple):
    """Static comm plan for one (model, world, hook) triple: the flat spec the
    gradients vectorize through, the bucket partition, the hook, and the
    top-k density (ignored by the dense hooks)."""

    spec: "FlatParamSpec"  # noqa: F821 - tpuddp.training.step.FlatParamSpec
    buckets: Tuple[Tuple[int, int], ...]
    hook: str
    world: int
    density: float = DEFAULT_TOPK_DENSITY

    # -- properties ---------------------------------------------------------
    @property
    def compressed(self) -> bool:
        return self.hook != "none"

    @property
    def needs_residual(self) -> bool:
        return self.hook in EF_HOOKS

    # -- residual lifecycle -------------------------------------------------
    def init_residual(self, per_replica: bool) -> Optional[np.ndarray]:
        """Host zeros for ``TrainState.comm_state``: ``(world * total,)`` when
        the residual is per-replica (shard_map — placed ``P("data")`` so each
        replica owns its slice) or ``(total,)`` replicated (auto mode, where
        the hook quantizes the already-aggregated gradient)."""
        if not self.needs_residual:
            return None
        n = self.spec.total * (self.world if per_replica else 1)
        return np.zeros((n,), np.float32)

    # -- per-bucket compress/exchange (SUM over replicas + own kept part) ---
    def _exchange_bucket(self, b, axis_name):
        """One bucket through the hook's wire format: returns
        ``(summed_f32, kept_f32)`` where ``summed`` is the cross-replica SUM
        of every replica's decompressed payload (this replica's own payload
        when ``axis_name=None`` — the auto-mode emulation) and ``kept`` is
        what THIS replica's send survived the round trip as (the
        error-feedback subtrahend)."""
        from tpuddp.parallel import collectives as col

        if self.hook in ("bf16", "bf16_ef"):
            comp = b.astype(wire_dtype(self.hook))
            kept = comp.astype(jnp.float32)
            if axis_name is None:
                return kept, kept
            from jax import lax

            return lax.psum(comp, axis_name).astype(jnp.float32), kept
        if self.hook == "int8_ef":
            scale = int8_scale(b)
            q = quantize_int8(b, scale)
            kept = q.astype(jnp.float32) * scale
            if axis_name is None:
                return kept, kept
            return col.allgather_dequant_sum(q, scale, axis_name), kept
        if self.hook == "topk_ef":
            k = bucket_topk(int(b.shape[0]), self.density)
            from jax import lax

            _, idx = lax.top_k(jnp.abs(b), k)
            vals = jnp.take(b, idx)
            # whole-bucket scale, not top-k-only: max|vals| == max|b| on
            # finite buckets (top-k selects the max), and a NaN anywhere in
            # the bucket poisons the scale even if top_k's NaN ordering
            # happened not to select it — the guard-visibility contract
            scale = int8_scale(b)
            q = quantize_int8(vals, scale)
            kept = jnp.zeros_like(b).at[idx].set(q.astype(jnp.float32) * scale)
            if axis_name is None:
                return kept, kept
            return (
                col.allgather_topk_sum(idx, q, scale, int(b.shape[0]), axis_name),
                kept,
            )
        raise AssertionError(f"hook {self.hook!r} has no exchange")

    def _compressed_sum(self, send, axis_name):
        """The whole padded vector through the bucketed exchange: per-bucket
        compress + collective-SUM + decompress, reassembled, plus the kept
        (round-tripped) view of this replica's send."""
        from jax import lax

        sums, keeps = [], []
        for s, e in self.buckets:
            b = lax.slice(send, (s,), (e,))
            summed, kept = self._exchange_bucket(b, axis_name)
            sums.append(summed)
            keeps.append(kept)
        return jnp.concatenate(sums), jnp.concatenate(keeps)

    # -- in-jit hook pipeline ----------------------------------------------
    def reduce(self, grads, residual, axis_name):
        """The bucketed hook pipeline: grads tree in, cross-replica MEAN
        grads tree out, plus the new residual. ``axis_name=None`` is the
        auto-mode emulation (no collective; XLA already reduced);
        ``axis_name`` may be a tuple of mesh axis names (the factored
        ("host", "local") data mesh under a flat topology)."""
        from tpuddp.training.step import _tree_to_vec, _vec_to_tree

        g_vec = _tree_to_vec(grads, self.spec)
        send = g_vec if residual is None else g_vec + residual
        reduced, kept = self._compressed_sum(send, axis_name)
        if axis_name is not None:
            reduced = reduced / self.world
        new_residual = residual
        if self.needs_residual:
            new_residual = send - kept
        return _vec_to_tree(reduced, self.spec), new_residual

    def reduce_hierarchical(self, grads, residual, inner: str, outer: str):
        """The multi-hop reduction (``comm_topology="hierarchical"``) over a
        factored ``(outer, inner)`` = ``("host", "local")`` data mesh:

        1. **intra-host f32 reduce-scatter** over ``inner``: each local
           device ends with the host-sum of one contiguous 1/L shard of the
           send — full precision, the cheap ICI hop;
        2. **compressed inter-host exchange** over ``outer``: the shard
           (ONE bucket — the scatter already partitioned the vector) goes
           through the hook's wire format, so only the compressed payload
           crosses the slow inter-host link;
        3. **all-gather** over ``inner`` reassembles the full reduced vector
           on every device.

        Error feedback: the only lossy hop is (2), and its error is owned by
        exactly one (host, local) pair per shard — this replica's new
        residual is its shard's compression error placed at the shard's
        offset (zeros elsewhere), so the replica-axis SUM of residuals still
        equals the total un-sent error and the elastic
        :func:`redistribute_residual` rules apply unchanged. The residual
        re-enters step (1) next time at full f32 precision."""
        from jax import lax

        from tpuddp.training.step import _tree_to_vec, _vec_to_tree

        g_vec = _tree_to_vec(grads, self.spec)
        send = g_vec if residual is None else g_vec + residual
        shard = lax.psum_scatter(send, inner, scatter_dimension=0, tiled=True)
        if self.hook == "none":
            shard_sum, kept = lax.psum(shard, outer), shard
        else:
            single = self._replace(buckets=((0, int(shard.shape[0])),))
            shard_sum, kept = single._exchange_bucket(shard, outer)
        reduced = lax.all_gather(shard_sum, inner, tiled=True) / self.world
        new_residual = residual
        if self.needs_residual:
            shard_n = int(shard.shape[0])
            offset = lax.axis_index(inner) * shard_n
            new_residual = lax.dynamic_update_slice(
                jnp.zeros_like(send), shard - kept, (offset,)
            )
        return _vec_to_tree(reduced, self.spec), new_residual

    def reduce_scatter(self, g_vec, residual, axis_name):
        """The weight-update-sharding composition: compress the whole padded
        vector and exchange it so each replica receives the f32-decompressed
        MEAN gradient for its contiguous 1/N shard (aligned with its
        optimizer-moment shard) — ``psum_scatter`` in the wire dtype for the
        bf16 hooks; the structured int8/top-k payloads are exchanged whole
        (one bucket — the scatter would scramble index ownership) and the
        own shard sliced from the decompressed sum. Returns
        ``(g_shard_mean_f32, new_residual)``; the residual stays full-length
        and local (it is this replica's compression error over the whole
        vector, not its shard's)."""
        from jax import lax

        from tpuddp.parallel.collectives import psum_scatter_compressed

        send = g_vec if residual is None else g_vec + residual
        if self.hook in ("bf16", "bf16_ef"):
            shard, comp = psum_scatter_compressed(
                send, wire_dtype(self.hook), axis_name
            )
            kept = comp.astype(jnp.float32)
        else:
            single = self._replace(buckets=((0, self.spec.total),))
            summed, kept = single._exchange_bucket(send, axis_name)
            shard_n = self.spec.total // self.world
            shard = lax.dynamic_slice(
                summed, (lax.axis_index(axis_name) * shard_n,), (shard_n,)
            )
        shard = shard / self.world
        new_residual = residual
        if self.needs_residual:
            new_residual = send - kept
        return shard, new_residual

def make_grad_comm(
    params,
    world: int,
    comm_hook: str = "none",
    bucket_cap_mb: float = DEFAULT_BUCKET_CAP_MB,
    flat_spec=None,
    density: float = DEFAULT_TOPK_DENSITY,
    force: bool = False,
) -> Optional[GradComm]:
    """Build the comm plan for ``params`` (None for hook "none" — the legacy
    pmean path needs no plan; accounting for it comes from a bf16 plan's
    sibling via :func:`comm_bytes_for_hook` — unless ``force=True``, which
    the hierarchical topology uses: its multi-hop exchange needs the flat
    spec even uncompressed). ``flat_spec`` reuses an existing
    :class:`FlatParamSpec` (the weight-update-sharding one) so the residual
    aligns with the scattered vector."""
    validate_hook(comm_hook)
    if comm_hook == "none" and not force:
        return None
    if comm_hook == "topk_ef":
        bucket_topk(1, density)  # validate the density range eagerly
    from tpuddp.training.step import make_flat_param_spec

    spec = flat_spec if flat_spec is not None else make_flat_param_spec(params, world)
    buckets = make_buckets(spec.sizes, spec.total, bucket_cap_mb)
    return GradComm(
        spec=spec, buckets=buckets, hook=comm_hook, world=world,
        density=float(density),
    )


def _bucket_payload_bytes(hook: str, size: int, density: float) -> int:
    """Wire bytes of ONE ``size``-element bucket's payload under ``hook`` —
    the per-hook byte formula the accounting tests pin:

    - ``none``:    size x 4            (f32 values)
    - ``bf16``/``bf16_ef``: size x 2   (bf16 values)
    - ``int8_ef``: size x 1 + 4        (int8 values + one f32 scale)
    - ``topk_ef``: k x (1 + 4) + 4     (k int8 values + k int32 indices +
                                        one f32 scale), k = max(1,
                                        floor(size x density))
    """
    if hook == "int8_ef":
        return size * _INT8_BYTES + _SCALE_BYTES
    if hook == "topk_ef":
        k = bucket_topk(size, density)
        return k * (_INT8_BYTES + _IDX_BYTES) + _SCALE_BYTES
    return size * wire_itemsize(hook)


def comm_bytes_for_hook(
    params,
    world: int,
    comm_hook: str,
    wus: bool = False,
    wire: bool = True,
    bucket_cap_mb: float = DEFAULT_BUCKET_CAP_MB,
    density: float = DEFAULT_TOPK_DENSITY,
) -> int:
    """Analytic per-replica wire payload of ONE gradient reduction (bytes) —
    the counter the dryrun/bench compare across hooks: the payload bytes
    entering the gradient collective, in its wire format (values in the wire
    dtype, PLUS int32 indices for the sparse hook and the per-bucket f32
    scale scalars for the quantized hooks — side-channel bytes are wire
    bytes too). Ring-transfer multipliers (2(N-1)/N for allreduce, (N-1)/N
    for reduce-scatter/all-gather) are topology constants that cancel in any
    same-shape comparison, so the counter reports the payload itself — the
    quantity the hook changes. ``wus`` counts the gradient exchange as ONE
    whole-vector bucket (the scatter degenerates the bucket partition; the
    f32 parameter all-gather is a separate, hook-independent exchange).
    ``wire=False`` (``mode="auto"`` / the managed Accelerator, where XLA
    inserts the psum and the hook only emulates the quantization) accounts
    the collective at f32 regardless of hook — the counter must never record
    a byte cut that did not reach the wire."""
    validate_hook(comm_hook)
    from tpuddp.training.step import make_flat_param_spec

    spec = make_flat_param_spec(params, world)
    if not wire:
        comm_hook = "none"
    if comm_hook == "none" and not wus:
        # the tree-level pmean reduces exactly the raw (unpadded) leaf
        # elements; flat-vector paths carry the world-multiple padding
        return sum(spec.sizes) * _F32_BYTES
    if comm_hook == "none":
        return spec.total * _F32_BYTES
    if wus:
        return _bucket_payload_bytes(comm_hook, spec.total, density)
    buckets = make_buckets(spec.sizes, spec.total, bucket_cap_mb)
    return sum(
        _bucket_payload_bytes(comm_hook, e - s, density) for s, e in buckets
    )


def comm_bytes_breakdown(
    params,
    world: int,
    comm_hook: str,
    topology: str = "flat",
    local_size: Optional[int] = None,
    wire: bool = True,
    bucket_cap_mb: float = DEFAULT_BUCKET_CAP_MB,
    density: float = DEFAULT_TOPK_DENSITY,
) -> dict:
    """Per-replica wire bytes of ONE gradient reduction, split intra- vs
    inter-host — the accounting the hierarchical topology exists to move:

    - ``flat``: the whole payload is one collective over the undifferentiated
      data axis; accounted as inter-host (the conservative reading — on a
      multi-host pod every byte of a flat collective crosses the slowest
      link at least logically; on one host the column reads as ICI traffic).
    - ``hierarchical``: intra-host = the f32 reduce-scatter operand
      (``total`` x 4) plus the f32 all-gather operand (the ``total/L``
      shard x 4); inter-host = the hook's compressed payload of the
      ``total/L`` shard (ONE bucket — the scatter already partitioned).

    ``wire=False`` (auto/managed) reports the f32 flat payload, exactly like
    :func:`comm_bytes_for_hook`."""
    validate_hook(comm_hook)
    validate_topology(topology)
    from tpuddp.training.step import make_flat_param_spec

    total_flat = comm_bytes_for_hook(
        params, world, comm_hook, wire=wire,
        bucket_cap_mb=bucket_cap_mb, density=density,
    )
    if topology == "flat" or not wire:
        return {
            "total": total_flat, "inter_host": total_flat, "intra_host": 0,
        }
    if not local_size or world % local_size:
        raise ValueError(
            f"hierarchical accounting needs the inner-axis size (got "
            f"local_size={local_size!r} for world {world})"
        )
    spec = make_flat_param_spec(params, world)
    shard_n = spec.total // local_size
    intra = spec.total * _F32_BYTES + shard_n * _F32_BYTES
    inter = (
        shard_n * _F32_BYTES
        if comm_hook == "none"
        else _bucket_payload_bytes(comm_hook, shard_n, density)
    )
    return {"total": intra + inter, "inter_host": inter, "intra_host": intra}


def _leaf_roundtrip(s, hook: str, density: float):
    """One leaf through the hook's wire format and back (the auto-mode
    emulation: the leaf IS the bucket). Shape-preserving."""
    if hook in ("bf16", "bf16_ef"):
        return s.astype(wire_dtype(hook)).astype(jnp.float32)
    flat = jnp.ravel(s)
    scale = int8_scale(flat)
    if hook == "int8_ef":
        return (quantize_int8(flat, scale).astype(jnp.float32) * scale).reshape(
            s.shape
        )
    # topk_ef: keep density of the leaf, int8-quantized like the wire payload
    from jax import lax

    k = bucket_topk(int(flat.shape[0]), density)
    _, idx = lax.top_k(jnp.abs(flat), k)
    q = quantize_int8(jnp.take(flat, idx), scale)
    dense = jnp.zeros_like(flat).at[idx].set(q.astype(jnp.float32) * scale)
    return dense.reshape(s.shape)


def local_quantize(grads, residual, hook: str, density: float = DEFAULT_TOPK_DENSITY):
    """Tree-level hook emulation for the managed/auto path: round-trip the
    (already globally-aggregated) gradient through the hook's wire format,
    with the same error-feedback residual semantics as the explicit path
    (each leaf is its own bucket: per-leaf int8 scale / per-leaf top-k).
    ``residual`` is a pytree like ``grads`` (or None for hook "bf16").
    Returns ``(quantized_grads, new_residual)``."""
    validate_hook(hook)
    if hook == "none":
        return grads, residual
    if hook == "bf16":
        return (
            jax.tree_util.tree_map(
                lambda g: _leaf_roundtrip(g, hook, density), grads
            ),
            residual,
        )
    send = jax.tree_util.tree_map(lambda g, r: g + r, grads, residual)
    quant = jax.tree_util.tree_map(
        lambda s: _leaf_roundtrip(s, hook, density), send
    )
    new_residual = jax.tree_util.tree_map(lambda s, q: s - q, send, quant)
    return quant, new_residual


def init_residual_tree(params):
    """Zeros-like residual pytree for :func:`local_quantize`'s bf16_ef."""
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(np.shape(p), jnp.float32), params
    )


# run_meta's ``comm.overlap`` (schema v10): the exchange trails the backward
# pass in the one step program, so the record is a constant. Kept because
# benchmark/run.py and the schema read it (ROADMAP D12).
OVERLAP_META = {"enabled": False, "segments": None, "reason": "barrier step"}


def redistribute_residual(mat: np.ndarray, new_world: int) -> Tuple[np.ndarray, str]:
    """Re-map per-replica error-feedback residuals onto a new world size —
    the elastic-resume rule for ``TrainState.comm_state`` (DynamiQ's
    dynamic-world-size compression-state motivation, arxiv.org/abs/2602.08923).

    ``mat`` is the residual viewed as ``(old_world, per)``: row ``r`` is
    replica ``r``'s accumulated compression error over the whole flat
    gradient vector. What steers the trajectory is the SUM over replicas —
    each replica adds its residual into its next send and the sends are
    ``psum``'d — so any re-mapping that preserves the per-element sum over
    the replica axis preserves the aggregate un-sent error budget:

    - shrink, ``new_world`` divides ``old_world``: each new replica takes the
      elementwise f32 sum of one group of ``old/new`` consecutive old rows
      (``reshape(new, k, per).sum(axis=1)`` — exactly reproducible, so tests
      can assert the redistribution bitwise);
    - grow, ``old_world`` divides ``new_world``: old row ``r`` moves verbatim
      to new row ``r * (new/old)``; the other rows start at zero (pure
      placement — bitwise sum-preserving);
    - no divisor relation (``M∤N`` both ways): there is no sum-preserving
      alignment of whole rows, so the residual RESETS to zero — the
      documented fallback. The un-sent error (bounded by one step's bf16
      rounding per element) is dropped once; callers record a typed
      ``comm_state_reset`` event row so the discontinuity is auditable.

    Returns ``(new_mat, action)`` with ``action`` one of ``"unchanged"`` /
    ``"redistributed"`` / ``"reset"``."""
    mat = np.asarray(mat)
    if mat.ndim != 2:
        raise ValueError(f"expected a (world, per) residual view, got {mat.shape}")
    old_world, per = mat.shape
    if new_world < 1:
        raise ValueError(f"new_world must be >= 1, got {new_world}")
    if new_world == old_world:
        return mat, "unchanged"
    if old_world % new_world == 0:
        k = old_world // new_world
        return mat.reshape(new_world, k, per).sum(axis=1), "redistributed"
    if new_world % old_world == 0:
        k = new_world // old_world
        out = np.zeros((new_world, per), mat.dtype)
        out[::k] = mat
        return out, "redistributed"
    return np.zeros((new_world, per), mat.dtype), "reset"
