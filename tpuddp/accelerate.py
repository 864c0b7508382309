"""Accelerator — the managed two-level-API facade (SURVEY.md §2b #15).

Mirrors the HuggingFace ``Accelerator`` surface the reference's second
entrypoint uses (multi-GPU-training-accelerate.py:115-131,53,96,104-108):
``prepare``, ``backward``, ``device``, ``is_local_main_process``,
``is_main_process``, ``wait_for_everyone``, ``save_model``, ``gather`` — and
routes every one of them through the SAME mesh/collectives backend as the
explicit DistributedDataParallel API (the two-level contract of SURVEY.md §1).

JAX is functional, so the torch-imperative sequence

    outputs = model(inputs)          # forward
    loss = criterion(outputs, labels)
    accelerator.backward(loss)       # backward + grad sync
    optimizer.step()                 # param update

is bridged lazily: ``model(inputs)`` returns a :class:`LazyForward` and
``criterion(...)`` a :class:`LazyLoss`; nothing runs until
``accelerator.backward(loss)``, which executes ONE jitted global-batch
value_and_grad over the data-sharded mesh (gradient cross-replica reduction
falls out of XLA's data flow — the managed analog of DDP's allreduce),
stashes the averaged grads, and caches the loss value so a later
``loss.item()`` is free. ``optimizer.step()`` then applies the native
optimizer update. ``zero_grad()`` is the traditional no-op.

Managed-mode BatchNorm note: batch statistics are computed over the *global*
sharded batch under jit, i.e. SyncBatchNorm semantics by construction — the
behavior the reference README recommends turning on (README.md:79-81).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpuddp import optim as optim_lib
from tpuddp import seeding
from tpuddp.data.loader import DataLoader, ShardedDataLoader
from tpuddp.nn.core import Context, Module
from tpuddp.parallel import collectives as col
from tpuddp.parallel import comm as comm_lib
from tpuddp.parallel.mesh import data_mesh, replicate, shard_batch
from tpuddp.resilience import guard as guard_lib
from tpuddp.training import checkpoint as ckpt
from tpuddp.utils import batching


class LazyForward:
    """Deferred forward pass: records (model, inputs); materializes on demand."""

    def __init__(self, model: "PreparedModel", x):
        self._model = model
        self._x = x
        self._logits = None
        self._weights = None  # sample weights bound by a criterion, if any

    # hook consumed by tpuddp criterions (see nn/loss.py)
    def _tpuddp_bind_loss(self, criterion, labels, weights=None):
        # remember the batch weights so a train-mode materialization of THIS
        # forward masks padded rows out of BatchNorm statistics, same as the
        # grad/fused/scan steps do
        self._weights = weights
        return LazyLoss(self, criterion, labels, weights)

    @property
    def value(self):
        if self._logits is None:
            self._logits = self._model._forward_concrete(self._x, self._weights)
        return self._logits

    def __array__(self, dtype=None):
        arr = np.asarray(self.value)
        return arr.astype(dtype) if dtype is not None else arr

    def argmax(self, axis=-1):
        return jnp.argmax(self.value, axis=axis)


class LazyLoss:
    """Deferred loss: executed by ``Accelerator.backward`` (fused fwd+bwd) or
    by ``.item()`` (forward only, e.g. in eval loops)."""

    def __init__(self, fwd: LazyForward, criterion, labels, weights):
        self._fwd = fwd
        self._criterion = criterion
        self._labels = labels
        self._weights = weights
        self._value = None
        self._backward_requested = False
        self._dropped = False  # backward request superseded/cleared unexecuted
        self._drop_reason = (
            "a second accelerator.backward() or zero_grad() preceded "
            "optimizer.step()"
        )
        self._queued_on = None  # PreparedOptimizer holding this in a fuse queue
        self._value_src = None  # (losses_array, i) from a fused-scan flush

    def _run_backward(self):
        model = self._fwd._model
        self._backward_requested = True
        model._begin_backward(
            self._fwd._x, self._labels, self._weights, self._criterion, self
        )

    def device_value(self):
        """The loss as a device scalar with NO host sync — the deferred-metrics
        accumulator primitive (quirk Q5: ``loss.item()`` per batch is the
        reference's per-batch device sync; this is the opt-out)."""
        if self._value is None and self._queued_on is not None:
            # this loss sits in a fuse_steps queue: execute the queued steps
            # (one scan dispatch), which assigns every queued loss's value
            self._queued_on.flush()
        if self._value is None and self._value_src is not None:
            # lazily slice out of the flush's (K,) loss stack — only losses
            # actually read cost a dispatch (sum_losses never takes this path)
            arr, i = self._value_src
            self._value = arr[i]
        if self._value is None:
            model = self._fwd._model
            if model._pending is not None and model._pending[-1] is self:
                # backward was requested but step() hasn't fused it yet:
                # materialize grads + loss now (grad-only program)
                model._materialize_grads()
        if self._value is None and self._dropped:
            # The pending backward was superseded (second backward before
            # step()) or cleared (zero_grad); a recompute here would use the
            # CURRENT params and a fresh RNG key and silently return a value
            # different from the loss that was requested — refuse instead.
            raise RuntimeError(
                "this loss's backward request was dropped before it executed "
                f"({self._drop_reason}); its value was never computed."
            )
        if self._value is None:
            # forward-only path (no backward requested, e.g. eval loops)
            logits = jnp.asarray(self._fwd.value)
            self._value = self._criterion(
                logits, jnp.asarray(self._labels), self._weights
            )
        return self._value

    def item(self) -> float:
        return float(self.device_value())

    def __float__(self):
        return self.item()


def sum_losses(losses, initial=None):
    """Epoch-end device sum of many :class:`LazyLoss` values with the fewest
    device ops: losses that came out of the same fused-scan flush share one
    ``(K,)`` loss array and are summed array-at-a-time (two ops per flush)
    instead of scalar-at-a-time (two ops per batch — measured to dominate the
    steps themselves on dispatch-latency-bound runtimes). Returns a device
    scalar (0.0 for an empty sequence); ``float()`` it for the host value.
    ``initial`` seeds the sum — an exact mid-epoch resume carries the
    interrupted run's partial loss total through it."""
    import jax.numpy as _jnp

    losses = list(losses)
    if not losses:
        return _jnp.asarray(0.0 if initial is None else initial)
    for l in losses:
        if l._value is None and l._queued_on is not None:
            l._queued_on.flush()  # one flush settles every queued loss
    total = None if initial is None else _jnp.asarray(initial)
    by_stack = {}  # id(array) -> [array, [indices]]
    for l in losses:
        if l._value is None and l._value_src is not None:
            arr, i = l._value_src
            by_stack.setdefault(id(arr), [arr, []])[1].append(i)
        else:
            v = l.device_value()
            total = v if total is None else total + v
    for arr, idxs in by_stack.values():
        s = _jnp.sum(arr) if len(idxs) == arr.shape[0] else _jnp.sum(arr[_jnp.asarray(idxs)])
        total = s if total is None else total + s
    return total


class StagedUploadLoader:
    """Upload lookahead for the managed loop: issues batch N+1's host->device
    transfer (``jnp.asarray`` of the input tensor) before batch N is yielded,
    so the transfer rides the runtime's async stream while batch N's step is
    still recording/executing — the managed analog of the native epoch
    driver's staged chunks (training/loop.py). Yields ``(x_on_device, y, w)``
    with values and order unchanged; labels/weights stay host-side (they are
    small and the train step re-shards them anyway)."""

    def __init__(self, loader):
        self.loader = loader

    def set_epoch(self, epoch: int) -> None:
        self.loader.set_epoch(epoch)

    def __len__(self) -> int:
        return len(self.loader)

    def __getattr__(self, name):
        return getattr(self.loader, name)

    def __iter__(self):
        # multi-host shard_batch consumes process-local HOST data (its
        # make_array_from_process_local_data branch would round-trip a device
        # array back through np.asarray), so staging only helps — and only
        # runs — on single-process worlds
        put = jnp.asarray if jax.process_count() == 1 else (lambda a: a)
        prev = None
        for x, y, w in self.loader:
            cur = (put(x), y, w)  # issue the upload one batch early
            if prev is not None:
                yield prev
            prev = cur
        if prev is not None:
            yield prev


class FusedEvaluator:
    """One-dispatch-per-K-batches managed eval — the managed analog of the
    native ``build_eval_scan_step``. The facade eval loop costs 2+ dispatches
    per test batch (transform, forward) plus per-batch metric ops; this
    accumulator queues K batches and runs transform + forward + loss +
    correct/count accumulation as ONE jitted scan, carrying the running
    ``(loss_sum, correct, n)`` device scalars through the program so no
    eager per-batch arithmetic is dispatched at all.

    Reference semantics preserved (quirk Q3, multi-GPU-training-accelerate.py
    :60-75): every process evaluates the FULL unsharded test stream, the loss
    totalled is the per-batch criterion mean, and padded rows (w == 0) are
    excluded from both correctness counts and the criterion's weighting.

    Usage::

        ev = FusedEvaluator(model, criterion, transform=eval_transform)
        for x, y, w in test_loader:
            ev.add(x, y, w)
        loss_sum, correct, total = ev.finalize()
    """

    def __init__(self, model: "PreparedModel", criterion, transform=None,
                 fuse_steps=None, stage_uploads: bool = True):
        self.model = model
        self.criterion = criterion
        self.transform = transform
        # async-pipeline eval staging: each add()'d batch's host->device
        # transfer is issued IMMEDIATELY (device_put is async), so chunk
        # N+1's upload overlaps chunk N's scan dispatch instead of paying
        # K serial transfers at flush time. Single-process only: the
        # multi-host flush replicates process-local HOST data. Values and
        # order are unchanged — bitwise-identical metrics, ragged tails
        # included (tests/test_pipeline.py).
        self.stage_uploads = bool(stage_uploads) and jax.process_count() == 1
        # None = resolved at first use (flat 32, capped by the staging
        # budget over the batch bytes — the same policy as the train-side
        # fuse auto; see _resolve_auto_fuse)
        self.fuse_steps = None if fuse_steps is None else max(1, int(fuse_steps))
        self._queue = []
        self._stats = None
        self._progs = {}
        # auto-depth cache, keyed by the queued batch's shape_key: on ragged
        # streams the depth is RE-derived (and re-capped by the staging
        # budget) whenever the batch shape changes — a depth pinned by an
        # early small batch must not let a later large batch stage
        # depth x batch bytes past the ~256 MB budget
        self._fuse_cache = None  # (shape_key, resolved depth)

    def _resolve_fuse(self) -> int:
        if self.fuse_steps is not None:
            return self.fuse_steps
        batch_nbytes = None
        shape_key = None
        if self._queue:
            # .nbytes is metadata on both numpy and jax arrays — never
            # np.asarray a queued x here, it may be a staged device array
            # and the conversion would force a host transfer
            shape_key = self._queue[0][0]
            batch_nbytes = getattr(self._queue[0][1], "nbytes", None)
        params = self.model._params
        if params is None or params is _LOST_TO_FAILED_FLUSH or not self._queue:
            # don't cache while the model is unresolved OR before a real
            # batch is in hand (an empty-queue probe would pin the uncapped
            # depth and bypass the staging budget for the evaluator's life)
            return _resolve_auto_fuse(None, batch_nbytes)
        if self._fuse_cache is None or self._fuse_cache[0] != shape_key:
            self._fuse_cache = (
                shape_key, _resolve_auto_fuse(params, batch_nbytes)
            )
        return self._fuse_cache[1]

    def add(self, x, y, w=None):
        if w is None:
            w = np.ones(len(y), np.float32)
        # metadata-only key (shared with serving's scheduler): x may be a
        # staged device array and np.asarray on it would force a transfer
        shape_key = batching.shape_key(x)
        if self._queue and self._queue[0][0] != shape_key:
            self._flush()  # ragged stream: never stack mixed shapes
        if self.stage_uploads:
            # issue this batch's upload now, overlapping the previous
            # flush's in-flight dispatch (no-op for already-device arrays)
            x, y, w = jnp.asarray(x), jnp.asarray(y), jnp.asarray(w)
        self._queue.append((shape_key, x, y, w))
        if len(self._queue) >= self._resolve_fuse():
            self._flush()

    def _get_prog(self, k: int):
        if k not in self._progs:
            module, criterion, transform = (
                self.model.module, self.criterion, self.transform,
            )

            def prog(params, mstate, stats, xs, ys, ws):
                stacked = (jnp.stack(xs), jnp.stack(ys), jnp.stack(ws))

                def body(carry, inp):
                    x, y, w = inp
                    if transform is not None:
                        x = transform(x)
                    ctx = Context(train=False, rng=jax.random.key(0), axis_name=None)
                    logits, _ = module.apply(params, mstate, x, ctx)
                    loss = criterion(logits, y, w)
                    pred = jnp.argmax(logits, axis=-1)
                    mask = w > 0
                    # counts carry as int32 — f32 accumulation silently stops
                    # incrementing past 2^24 on long eval streams
                    correct = jnp.sum(
                        jnp.where(mask, pred == jnp.asarray(y), False).astype(jnp.int32)
                    )
                    n = jnp.sum(mask.astype(jnp.int32))
                    l0, c0, n0 = carry
                    return (l0 + loss, c0 + correct, n0 + n), None

                out, _ = jax.lax.scan(body, stats, stacked)
                return out

            self._progs[k] = jax.jit(prog)
        return self._progs[k]

    def _flush(self):
        queue, self._queue = self._queue, []
        if not queue:
            return
        model = self.model
        model._flush_queues()  # queued train updates must land first
        model._check_not_lost()
        if model._params is None:
            raise RuntimeError(
                "FusedEvaluator needs an initialized model: run one forward "
                "or a training step before evaluating"
            )
        if self._stats is None:
            stats = (
                jnp.zeros((), jnp.float32),  # loss sum
                jnp.zeros((), jnp.int32),    # correct
                jnp.zeros((), jnp.int32),    # weighted row count
            )
            if jax.process_count() > 1:
                # the global-mesh jit below needs global arrays for EVERY
                # input; the carried stats are global from the first flush's
                # output onward, but these initial zeros must be placed too
                stats = replicate(model.accelerator.mesh, stats)
            self._stats = stats
        fn = self._get_prog(len(queue))
        xs = tuple(jnp.asarray(e[1]) for e in queue)
        ys = tuple(jnp.asarray(e[2]) for e in queue)
        ws = tuple(jnp.asarray(e[3]) for e in queue)
        if jax.process_count() > 1:
            # multi-host: the jit over the global mesh needs global arrays;
            # every process holds the same full test batch (quirk Q3), so
            # replication is well-defined (same invariant as
            # PreparedModel._forward_concrete)
            xs, ys, ws = replicate(model.accelerator.mesh, (xs, ys, ws))
        self._stats = fn(model._params, model._model_state, self._stats, xs, ys, ws)

    def finalize(self):
        """Flush the remainder and fetch once. Returns host
        ``(loss_sum, correct, total)``."""
        self._flush()
        if self._stats is None:
            return 0.0, 0, 0
        sums = jax.device_get(self._stats)
        self._stats = None
        return float(sums[0]), int(sums[1]), int(sums[2])


class _FlatShardedUpdate(optim_lib.Optimizer):
    """GSPMD weight-update sharding for the managed path (the jit/auto analog
    of the shard_map path's explicit reduce-scatter/all-gather —
    arxiv.org/abs/2004.13336, ZeRO-1): presents the wrapped optimizer's
    tree-pytree API while storing its state as ONE flat padded f32 vector
    whose sharding is constrained over the data axis. Under ``jit``, XLA's
    partitioner then computes each parameter-shard's update on the chip that
    owns the moment shard, without any explicit collective in the program.
    The sharded STORAGE and partitioned update math are guaranteed (layout
    asserted in tests); the concrete collective the partitioner derives for
    the gradient exchange is backend-dependent (the TPU partitioner forms
    reduce-scatter for this pattern; the CPU test backend emits
    all-reduce + gather). The native shard_map path spells the
    reduce-scatter/all-gather out explicitly — and its compiled HLO is
    asserted to contain exactly that exchange
    (tests/test_weight_update_sharding.py)."""

    def __init__(self, inner, spec, mesh):
        from tpuddp.parallel.mesh import data_sharded, replicated as rep_sharding

        self.inner = inner
        self.spec = spec
        self.mesh = mesh
        self._sharded = data_sharded(mesh)
        self._replicated = rep_sharding(mesh)

    def _is_vec(self, leaf) -> bool:
        shape = getattr(leaf, "shape", None)
        return shape is not None and len(shape) == 1 and shape[0] == self.spec.total

    def init(self, params):
        """Create the flat state ALREADY sharded: jit with per-leaf
        out_shardings, so XLA materializes each chip's zero shard in place —
        no full-size single-device allocation, no host round trip."""
        def make():
            return self.inner.init(jnp.zeros((self.spec.total,), jnp.float32))

        shaped = jax.eval_shape(make)
        out_sh = jax.tree_util.tree_map(
            lambda l: self._sharded if self._is_vec(l) else self._replicated,
            shaped,
        )
        return jax.jit(make, out_shardings=out_sh)()

    def place_state(self, opt_state):
        """Lay a HOST-side flat state (a checkpoint restore) out over the
        mesh: (total,) vectors sharded over the data axis, scalars
        replicated (via the multi-host-safe replicate helper)."""
        def place(leaf):
            if self._is_vec(leaf):
                host = np.asarray(leaf)
                return jax.make_array_from_callback(
                    host.shape, self._sharded, lambda idx: host[idx]
                )
            return replicate(self.mesh, leaf)

        return jax.tree_util.tree_map(place, opt_state)

    def update(self, grads, opt_state, params):
        from jax.lax import with_sharding_constraint as wsc

        from tpuddp.training.step import _tree_to_vec, _vec_to_tree

        g_vec = wsc(_tree_to_vec(grads, self.spec), self._sharded)
        p_vec = _tree_to_vec(params, self.spec)
        update_flat = getattr(self.inner, "update_flat", None)
        if update_flat is not None:
            # LARS/LAMB: per-layer trust ratios over the spec's leaf
            # boundaries — the full vector is logically in hand here (XLA
            # partitions the segment sums), so no explicit collective
            new_p_vec, new_os = update_flat(
                g_vec, opt_state, p_vec, spec=self.spec
            )
        else:
            new_p_vec, new_os = self.inner.update(g_vec, opt_state, p_vec)
        # pin the state sharded (stable layout across steps/donation) and the
        # params replicated (the all-gather point)
        new_os = jax.tree_util.tree_map(
            lambda l: wsc(l, self._sharded) if self._is_vec(l) else l, new_os
        )
        new_p_vec = wsc(new_p_vec, self._replicated)
        return _vec_to_tree(new_p_vec, self.spec), new_os


def _resolve_auto_fuse(params, batch_nbytes=None) -> int:
    """The managed auto fusion depth: 32, capped by the SAME ~256 MB
    staged-bytes budget as the native ``scan_steps: auto``
    (training/loop.py) when the per-batch input bytes are known — the queue
    holds K device batches before each flush, so depth × batch bytes is
    real HBM. Shared by the train-side fuse_steps="auto" and the
    FusedEvaluator so the two can't drift apart.

    Big models used a shallower flat 8 through r4 (per-batch sharded
    placement flattens the scaling), but the r5 full-bench managed-AlexNet
    row measured fuse=32 within 2.9% of the native K-fused step — depth
    amortizes the per-dispatch latency.
    ``params`` stays in the signature as the size hook should the policy
    become size-keyed again. The budget-cap arithmetic is the shared
    implementation in ``tpuddp/utils/batching.py`` (one policy for eval
    fusion, managed train fusion, and serving's device queues)."""
    del params
    return batching.resolve_fuse(batch_nbytes, cap=32)


# fold_in tag deriving the in-step augmentation key from the step's base rng:
# the dropout stream (Context rng) stays byte-identical whether augment is
# folded into the step or not
_AUG_FOLD = 0x617567  # "aug"


def _apply_step_augment(aug, rng, x):
    """On-device augmentation inside the compiled train step (the async
    pipeline's 'host workers only decode and stack' contract for the managed
    path): keyed off a fold of the step rng so the flip decisions are
    per-step deterministic and the model's own rng stream is untouched."""
    if aug is None:
        return x
    return aug(jax.random.fold_in(rng, _AUG_FOLD), x)


class _LostState:
    """Sentinel for model variables whose device buffers were donated to a
    fused dispatch that then failed — any read must fail loudly."""

    def __repr__(self):  # pragma: no cover - debugging aid
        return "<model state lost to a failed fused dispatch>"


_LOST_TO_FAILED_FLUSH = _LostState()


class PreparedModel:
    """The managed model: owns params/buffers, a compiled sharded train
    grad-step, and compiled replicated inference forwards. Mode toggles
    (``train()``/``eval()``) mirror ``nn.Module`` semantics."""

    def __init__(self, accelerator: "Accelerator", module: Module):
        self.accelerator = accelerator
        self.module = module
        self._params = None
        self._model_state = None
        self._training = True
        self._grad_step = None
        self._fused_step = None
        self._fused_scans = {}
        self._fwd = {}
        self._pending = None  # (x, y, w, criterion, step_idx, LazyLoss)
        self._pending_grads = None
        # model_state as of BEFORE the last grad-only forward: the guard's
        # skip branch reverts to it so a poisoned forward's BatchNorm stats
        # never outlive a skipped update (grad-only programs commit
        # _model_state eagerly, unlike the fused step whose cond owns it)
        self._mstate_before = None
        self._ones = {}  # cached sharded all-ones weight vectors by length
        self._bwd_key = accelerator._next_key()  # base key; fold_in(step) per batch
        self._bwd_counter = 0

    # -- torch-parity mode switches --
    def train(self):
        self._training = True
        return self

    def eval(self):
        self._training = False
        return self

    # Reading the variables flushes any queued fused steps first — a direct
    # `model.params` read (weight-norm logging, accelerator.gather) must
    # never see values that are K queued updates stale. Internal code that
    # runs *during* a flush touches `_params` directly (the queue is popped
    # at flush entry, so the re-entrant flush callback is a no-op, but
    # skipping the property keeps the hot path cheap).
    def _check_not_lost(self):
        if self._params is _LOST_TO_FAILED_FLUSH:
            raise RuntimeError(
                "the model's device buffers were donated to a fused-step "
                "dispatch that failed mid-execution; the parameters no "
                "longer exist. Restore from a checkpoint "
                "(accelerator.load_model) before continuing."
            )

    @property
    def params(self):
        self._flush_queues()
        self._check_not_lost()
        return self._params

    @params.setter
    def params(self, value):
        self._params = value

    @property
    def model_state(self):
        self._flush_queues()
        self._check_not_lost()
        return self._model_state

    @model_state.setter
    def model_state(self, value):
        self._model_state = value

    def _guard_enabled(self) -> bool:
        g = getattr(self.accelerator, "guard", None)
        return bool(g is not None and g.enabled)

    def _ensure_init(self, x):
        if self._params is not None:  # backing field: must not flush the queue
            return
        # Pretrained fine-tune hook: a module carrying pre-loaded variables
        # (tpuddp.models.torch_import.load_pretrained_alexnet) starts from
        # them instead of a fresh init.
        preloaded = getattr(self.module, "_tpuddp_initial_variables", None)
        if preloaded is not None:
            params, mstate = preloaded
        else:
            key = self.accelerator._next_key()
            sample = jax.ShapeDtypeStruct(
                (1,) + tuple(np.shape(x))[1:], jnp.asarray(x[:1]).dtype
            )
            aug = getattr(self.accelerator, "augment", None)
            if aug is not None:
                # in-step augmentation: the module sees the POST-augment
                # shape/dtype (e.g. uint8 32x32 decoded batches resized to
                # the compute dtype at 224) — derive it abstractly, nothing
                # executes
                sample = jax.eval_shape(
                    lambda v: aug(jax.random.key(0), v), sample
                )
                sample = jax.ShapeDtypeStruct(sample.shape, sample.dtype)
            params, mstate = self.module.init(key, sample)
        params, mstate = col.broadcast_one_to_all((params, mstate))
        self.params, self.model_state = replicate(
            self.accelerator.mesh, (params, mstate)
        )
        if self._guard_enabled():
            # prepare-time desync audit (the managed analog of the DDP
            # wrap-time verify): every replica's copy of the just-placed
            # parameters must fingerprint identically before the first step
            guard_lib.audit_or_raise(
                self.accelerator.mesh, self._params, where="accelerator-prepare"
            )

    def __call__(self, x) -> LazyForward:
        self._ensure_init(x)
        return LazyForward(self, x)

    # -- concrete executions --
    def _maybe_clip(self, grads):
        clip = getattr(self.accelerator, "clip_grad_norm", None)
        if clip is None:
            return grads
        clipped, _ = optim_lib.clip_grad_norm_(grads, clip)
        return clipped

    def _flush_queues(self):
        """Execute any queued fused steps so ``params``/``model_state`` are
        current before they are read (forward, save, gather)."""
        cb = getattr(self, "_flush_cb", None)
        if cb is not None:
            cb()

    def _forward_concrete(self, x, w=None):
        """Replicated-batch forward (used for eval / output materialization).
        Unprepared eval loaders feed the FULL batch to every process — the
        reference's accelerate eval behavior (quirk Q3). In train mode the
        batch's sample weights (``w``, bound when a criterion was applied to
        this forward) mask padded rows out of BatchNorm batch statistics —
        consistent with the grad/fused/scan steps; a bare train-mode
        ``model(x)`` with no criterion has no weights and treats every row as
        real (the new model_state is discarded either way)."""
        self._flush_queues()  # queued updates must land before params are read
        self._check_not_lost()
        train = self._training
        has_w = train and w is not None
        key = (np.shape(x), train, has_w)
        if key not in self._fwd:
            if has_w:
                def fwd(params, mstate, xv, wv, rng):
                    ctx = Context(
                        train=True, rng=rng, axis_name=None, sample_weight=wv
                    )
                    logits, _ = self.module.apply(params, mstate, xv, ctx)
                    return logits
            else:
                def fwd(params, mstate, xv, rng):
                    ctx = Context(train=train, rng=rng, axis_name=None)
                    logits, _ = self.module.apply(params, mstate, xv, ctx)
                    return logits

            self._fwd[key] = jax.jit(fwd)
        rng = self.accelerator._next_key() if train else jax.random.key(0)
        xr = jnp.asarray(x)
        args = (xr,)
        if has_w:
            args = (xr, jnp.asarray(w))
        if jax.process_count() > 1:
            # multi-host: the jit needs a global array (a plain local array
            # cannot address remote devices); every process holds the same
            # full batch (quirk Q3), so replication is well-defined
            args = replicate(self.accelerator.mesh, args)
        # single-process: pass the local array straight in — the jit inserts
        # the (async) transfer itself; an eager replicate() here is a
        # dispatch of its own on the per-batch facade eval path
        return self._fwd[key](self._params, self._model_state, *args, rng)

    def _get_grad_step(self, criterion):
        if self._grad_step is None or self._grad_step[0] is not criterion:
            aug = getattr(self.accelerator, "augment", None)

            def grad_step(params, mstate, base_rng, step_idx, x, y, w):
                rng = jax.random.fold_in(base_rng, step_idx)
                x = _apply_step_augment(aug, rng, x)

                def loss_fn(p):
                    # sample_weight masks padded rows out of BatchNorm
                    # statistics (see nn/norm.py), matching the native path
                    ctx = Context(
                        train=True, rng=rng, axis_name=None, sample_weight=w
                    )
                    logits, new_mstate = self.module.apply(p, mstate, x, ctx)
                    return criterion(logits, y, w), new_mstate

                (loss, new_mstate), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params)
                return loss, grads, new_mstate

            self._grad_step = (criterion, jax.jit(grad_step))
        return self._grad_step[1]

    def _shard_xyw(self, x, y, w):
        mesh = self.accelerator.mesh
        xb, yb = shard_batch(mesh, (jnp.asarray(x), jnp.asarray(y)))
        if w is None:
            n = len(y)
            if n not in self._ones:
                self._ones[n] = shard_batch(mesh, np.ones(n, np.float32))
            wb = self._ones[n]
        else:
            wb = shard_batch(mesh, jnp.asarray(w))
        return xb, yb, wb

    def _begin_backward(self, x, y, w, criterion, lazy_loss):
        """Record the backward request (torch's ``loss.backward()`` moment).

        Execution is deferred so ``optimizer.step()`` can run forward +
        backward + update as ONE fused jit dispatch; if the loss value is
        needed first (``item()`` before ``step()``), ``_materialize_grads``
        runs the grad-only program instead. The per-batch RNG key is
        ``fold_in(backward_base, batch_index)`` computed INSIDE the jitted
        step — an eager ``jax.random.split`` per batch would be a device
        dispatch of its own."""
        if self._pending is not None:
            if getattr(self.accelerator, "gradient_accumulation_steps", 1) > 1:
                raise RuntimeError(
                    "gradient accumulation requires optimizer.step() after "
                    "EACH accelerator.backward(): the step is accumulated, "
                    "not applied, until the cycle boundary — a second "
                    "backward here would silently drop the previous "
                    "micro-batch's gradient."
                )
            old = self._pending[-1]
            if old._value is None:
                old._dropped = True
        step_idx = self._bwd_counter
        self._bwd_counter += 1
        self._pending = (x, y, w, criterion, step_idx, lazy_loss)
        # truthy marker preserving the backward-before-step contract; real
        # grad arrays only materialize on the grad-only path
        self._pending_grads = self._pending

    def _materialize_grads(self):
        self._flush_queues()  # grads must differentiate the CURRENT params
        self._check_not_lost()
        x, y, w, criterion, step_idx, lazy_loss = self._pending
        xb, yb, wb = self._shard_xyw(x, y, w)
        fn = self._get_grad_step(criterion)
        loss, grads, new_mstate = fn(
            self._params, self._model_state, self._bwd_key, step_idx, xb, yb, wb
        )
        self._mstate_before = self._model_state
        self._model_state = new_mstate
        self._pending_grads = grads
        self._pending = None
        lazy_loss._value = loss

    def _comm_hook_name(self) -> str:
        return getattr(self.accelerator, "comm_hook", "none")

    def _comm_density(self) -> float:
        from tpuddp.parallel.comm import DEFAULT_TOPK_DENSITY

        return getattr(self.accelerator, "topk_density", DEFAULT_TOPK_DENSITY)

    def _get_fused_step(self, criterion, optimizer):
        key = (criterion, optimizer)
        if self._fused_step is None or self._fused_step[0] != key:
            hook = self._comm_hook_name()
            density = self._comm_density()
            guard_on = self._guard_enabled()
            aug = getattr(self.accelerator, "augment", None)

            def fused(
                params, mstate, opt_state, comm_state, skipped, base_rng,
                step_idx, x, y, w,
            ):
                rng = jax.random.fold_in(base_rng, step_idx)
                x = _apply_step_augment(aug, rng, x)

                def loss_fn(p):
                    # sample_weight masks padded rows out of BatchNorm
                    # statistics (see nn/norm.py), matching the native path
                    ctx = Context(
                        train=True, rng=rng, axis_name=None, sample_weight=w
                    )
                    logits, new_mstate = self.module.apply(p, mstate, x, ctx)
                    return criterion(logits, y, w), new_mstate

                (loss, new_mstate), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params)

                def apply_all():
                    # comm hook (managed emulation, parallel/comm.py):
                    # quantize the aggregated gradient through the wire dtype
                    # with error feedback BEFORE the clip, matching the
                    # native step's reduce-then-clip order
                    g, cs = comm_lib.local_quantize(
                        grads, comm_state, hook, density=density
                    )
                    g = self._maybe_clip(g)
                    new_params, new_opt = optimizer.update(g, opt_state, params)
                    return new_params, new_mstate, new_opt, cs

                if not guard_on:
                    new_params, out_mstate, new_opt, cs = apply_all()
                    return loss, new_params, out_mstate, new_opt, cs, skipped
                # firewall (resilience/guard.py): the grads here ARE the
                # XLA-aggregated global-batch f32 gradient — checked before
                # quantization; a non-finite step is a bitwise no-op on
                # params / opt-state / EF-residual / module buffers
                ok = guard_lib.tree_all_finite(grads)
                new_params, out_mstate, new_opt, cs, new_skipped = jax.lax.cond(
                    ok,
                    lambda: apply_all() + (guard_lib.reset_consecutive(skipped),),
                    lambda: (params, mstate, opt_state, comm_state,
                             guard_lib.bump_skip_counters(skipped)),
                )
                return loss, new_params, out_mstate, new_opt, cs, new_skipped

            self._fused_step = (
                key,
                jax.jit(fused, donate_argnums=(0, 1, 2, 3)),
            )
        return self._fused_step[1]

    def _get_fused_scan_step(self, criterion, optimizer, k: int):
        """K queued train steps as ONE jit dispatch: the managed analog of the
        native path's ``build_train_scan_step``. Takes the K sharded batches as
        tuples of arrays (stacked *inside* jit — stacking device arrays on the
        host would force a transfer) and returns the K per-step losses as one
        device array."""
        key = (criterion, optimizer, k)
        if key not in self._fused_scans:
            hook = self._comm_hook_name()
            density = self._comm_density()
            guard_on = self._guard_enabled()
            aug = getattr(self.accelerator, "augment", None)

            def fused_scan(
                params, mstate, opt_state, comm_state, skipped, base_rng,
                idxs, xs, ys, ws,
            ):
                stacked = (
                    idxs,
                    jnp.stack(xs),
                    jnp.stack(ys),
                    jnp.stack(ws),
                )

                def body(carry, inp):
                    p, ms, os_, cs, sk = carry
                    idx, x, y, w = inp
                    rng = jax.random.fold_in(base_rng, idx)
                    x = _apply_step_augment(aug, rng, x)

                    def loss_fn(pp):
                        ctx = Context(
                            train=True, rng=rng, axis_name=None, sample_weight=w
                        )
                        logits, new_ms = self.module.apply(pp, ms, x, ctx)
                        return criterion(logits, y, w), new_ms

                    (loss, new_ms), grads = jax.value_and_grad(
                        loss_fn, has_aux=True
                    )(p)

                    def apply_all():
                        # comm hook: same quantize -> clip -> update order as
                        # the single fused step; the error-feedback residual
                        # rides in the scan carry
                        g, cs2 = comm_lib.local_quantize(
                            grads, cs, hook, density=density
                        )
                        g = self._maybe_clip(g)
                        new_p, new_os = optimizer.update(g, os_, p)
                        return new_p, new_ms, new_os, cs2

                    if not guard_on:
                        return apply_all() + (sk,), loss
                    # firewall: per-scanned-step verdict on the f32
                    # aggregated gradient, pre-quantization; the skip
                    # counters ride the carry with the residual
                    ok = guard_lib.tree_all_finite(grads)
                    new_carry = jax.lax.cond(
                        ok,
                        lambda: apply_all() + (guard_lib.reset_consecutive(sk),),
                        lambda: (p, ms, os_, cs,
                                 guard_lib.bump_skip_counters(sk)),
                    )
                    return new_carry, loss

                (p, ms, os_, cs, sk), losses = jax.lax.scan(
                    body, (params, mstate, opt_state, comm_state, skipped),
                    stacked,
                )
                return p, ms, os_, cs, sk, losses

            self._fused_scans[key] = jax.jit(
                fused_scan, donate_argnums=(0, 1, 2, 3)
            )
        return self._fused_scans[key]


class PreparedOptimizer:
    """Wraps a tpuddp optimizer; ``step()`` applies the grads stashed by the
    last ``accelerator.backward`` (torch call-order parity)."""

    def __init__(self, optimizer: optim_lib.Optimizer, model: PreparedModel):
        self.optimizer = optimizer
        self.model = model
        self.opt_state = None
        self._update = None
        # fuse_steps > 1: step() queues sharded pending steps here and runs
        # them K at a time as one lax.scan dispatch (flush())
        self._queue = []
        # this optimizer's resolved fusion depth ("auto" resolves per MODEL,
        # from its size, at the first step — a shared Accelerator may drive
        # models of very different sizes, each deserving its own depth)
        self._fuse = None
        # gradient_accumulation_steps > 1: running device-side grad sum
        self._accum_grads = None
        self._accum_count = 0
        self._tree_add = None
        # comm_hook="bf16_ef": the persistent error-feedback residual (a
        # pytree like the gradients); None for stateless hooks
        self._comm_state = None
        # numerical guard (resilience/guard.py): the firewall's skip
        # counters ({"total", "consecutive"} int32 device scalars, the
        # managed seat of TrainState.skipped_steps); None when guard is off
        self._skipped = None
        # model_state as of the START of the current accumulation cycle —
        # the guard revert target when the whole cycle is skipped (the
        # cycle is the atomic update unit, native-parity)
        self._cycle_mstate = None
        # analytic per-update gradient-comm wire bytes (the counter), known
        # once the model's parameters exist
        self.grad_comm_bytes_per_step = None

    def zero_grad(self):
        if self.model._pending is not None:
            old = self.model._pending[-1]
            if old._value is None and old._backward_requested:
                old._dropped = True
        self.model._pending_grads = None
        self.model._pending = None

    def _ensure_opt_state(self):
        """Lazy optimizer-state init. Under
        ``Accelerator(weight_update_sharding=True)`` the optimizer is wrapped
        in :class:`_FlatShardedUpdate` first, so the moments are created flat
        and laid out SHARDED over the data axis."""
        if self.opt_state is not None:
            return
        model = self.model
        acc = model.accelerator
        if getattr(acc, "weight_update_sharding", False):
            if not isinstance(self.optimizer, _FlatShardedUpdate):
                from tpuddp.training.step import make_flat_param_spec

                spec = make_flat_param_spec(model.params, acc.mesh.devices.size)
                self.optimizer = _FlatShardedUpdate(self.optimizer, spec, acc.mesh)
            self.opt_state = self.optimizer.init(model.params)  # born sharded
        else:
            self.opt_state = self.optimizer.init(model.params)
        hook = getattr(acc, "comm_hook", "none")
        if hook in comm_lib.EF_HOOKS and self._comm_state is None:
            # every EF hook (bf16_ef/int8_ef/topk_ef) carries the same
            # pytree-shaped residual on this path; scales are recomputed
            # per step, never state
            self._comm_state = replicate(
                acc.mesh, comm_lib.init_residual_tree(model._params)
            )
        if model._guard_enabled() and self._skipped is None:
            self._skipped = replicate(acc.mesh, guard_lib.init_skip_counters())
        self.grad_comm_bytes_per_step = comm_lib.comm_bytes_for_hook(
            model._params, acc.mesh.devices.size, hook,
            wus=getattr(acc, "weight_update_sharding", False),
            # the managed path quantizes the XLA-aggregated gradient — the
            # collective itself stays f32, and the counter says so
            wire=False,
        )

    def step(self):
        model = self.model
        model._check_not_lost()
        if model._pending_grads is None:
            raise RuntimeError(
                "optimizer.step() called without a preceding accelerator.backward(loss)"
            )
        self._ensure_opt_state()
        if model._pending is not None:
            x, y, w, criterion, step_idx, lazy_loss = model._pending
            model._pending = None
            model._pending_grads = None
            xb, yb, wb = model._shard_xyw(x, y, w)
            accum = getattr(model.accelerator, "gradient_accumulation_steps", 1)
            if accum > 1:
                # grad-only program per micro-batch; ONE averaged (and then
                # clipped) update every `accum` steps — identical to one step
                # on the concatenated batch when micro-batches are equal-size
                fng = model._get_grad_step(criterion)
                loss, grads, new_mstate = fng(
                    model._params, model._model_state,
                    model._bwd_key, step_idx, xb, yb, wb,
                )
                model._mstate_before = model._model_state
                model._model_state = new_mstate
                lazy_loss._value = loss
                self._accumulate(grads, accum)
                return
            fuse = self._fuse
            if fuse is None:
                fuse = getattr(model.accelerator, "fuse_steps", 1)
                if fuse == "auto":
                    # resolved once per optimizer, at the first step, when a
                    # real batch is in hand: flat 32 capped by the staging
                    # budget over THIS batch's bytes (the queue holds K such
                    # batches on device before each flush)
                    fuse = _resolve_auto_fuse(
                        model._params, getattr(xb, "nbytes", None)
                    )
                self._fuse = fuse
            if fuse > 1:
                # queue the sharded step; K of them run as ONE scan dispatch.
                # Reading params/loss values before the queue fills triggers
                # an early flush, so semantics never depend on the queue.
                if self._queue and (
                    self._queue[0][3] is not criterion
                    # ragged stream (e.g. a raw smaller last batch from an
                    # unprepared loader): never stack mixed shapes/dtypes —
                    # flush the homogeneous prefix first (jnp.stack would
                    # silently promote a mixed-dtype stack)
                    or self._queue[0][0].shape != xb.shape
                    or self._queue[0][0].dtype != xb.dtype
                ):
                    self.flush()
                self._queue.append((xb, yb, wb, criterion, step_idx, lazy_loss))
                lazy_loss._queued_on = self
                model._flush_cb = self.flush
                if len(self._queue) >= fuse:
                    self.flush()
                return
            self._run_fused(xb, yb, wb, criterion, step_idx, lazy_loss)
            return
        # grads were materialized early (loss.item() before step())
        grads = model._pending_grads
        model._pending_grads = None
        accum = getattr(model.accelerator, "gradient_accumulation_steps", 1)
        if accum > 1:
            # an early loss read must not bypass accumulation (an immediate
            # full-scale update here would be a silent 4x-LR bug)
            self._accumulate(grads, accum)
            return
        fn = self._get_apply_update()
        guard_on = model._guard_enabled()
        mstates = (
            (model._mstate_before, model._model_state) if guard_on else None
        )
        try:
            (model.params, self.opt_state, self._comm_state, self._skipped,
             mstate) = fn(
                grads, self.opt_state, model.params, self._comm_state,
                self._skipped, mstates, 1.0,
            )
        except BaseException:
            self._poison_if_donated()
            raise
        if guard_on:
            model._model_state = mstate

    def _accumulate(self, grads, accum: int):
        """Fold one micro-batch's gradient into the running device-side sum;
        apply ONE averaged (then clipped) update at the cycle boundary."""
        model = self.model
        if self._accum_grads is None:
            # cycle start: remember the buffers as of BEFORE this cycle's
            # first forward — the guard reverts a skipped cycle to them
            self._cycle_mstate = model._mstate_before
            self._accum_grads = grads
        else:
            if self._tree_add is None:
                self._tree_add = jax.jit(
                    lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                    donate_argnums=(0,),
                )
            self._accum_grads = self._tree_add(self._accum_grads, grads)
        self._accum_count += 1
        if self._accum_count >= accum:
            self.flush_accumulation()

    def flush_accumulation(self):
        """Apply any partially-accumulated cycle now (averaged over the
        micro-batches actually seen) — the dataloader-end behavior of HF's
        ``accumulate()``. No-op when nothing is accumulated. Call at epoch
        end so a partial cycle neither leaks into the next epoch nor gets
        silently dropped at training end."""
        if self._accum_count == 0:
            return
        model = self.model
        fn = self._get_apply_update()
        guard_on = model._guard_enabled()
        mstates = (
            (self._cycle_mstate, model._model_state) if guard_on else None
        )
        try:
            (model._params, self.opt_state, self._comm_state, self._skipped,
             mstate) = fn(
                self._accum_grads, self.opt_state, model._params,
                self._comm_state, self._skipped, mstates,
                1.0 / self._accum_count,
            )
        except BaseException:
            self._poison_if_donated()
            raise
        if guard_on:
            model._model_state = mstate
        self._accum_grads = None
        self._accum_count = 0
        self._cycle_mstate = None

    def _get_apply_update(self):
        """Jitted scale -> comm hook -> clip -> optimizer.update (the hook and
        the clip always apply to the final, averaged gradient — never per
        micro-batch — matching the native cycle-boundary order). Under the
        guard, the finiteness verdict on the scaled f32 gradient (checked
        before quantization) gates the whole tail through ``lax.cond``."""
        if self._update is None:
            clip = getattr(self.model.accelerator, "clip_grad_norm", None)
            hook = self._comm_hook_name()
            density = self.model._comm_density()
            guard_on = self.model._guard_enabled()

            def apply(grads, opt_state, params, comm_state, skipped, mstates, scale):
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)

                def apply_all():
                    g, cs = comm_lib.local_quantize(
                        grads, comm_state, hook, density=density
                    )
                    if clip is not None:
                        g, _ = optim_lib.clip_grad_norm_(g, clip)
                    new_params, new_opt = self.optimizer.update(
                        g, opt_state, params
                    )
                    return new_params, new_opt, cs

                if not guard_on:
                    new_params, new_opt, cs = apply_all()
                    return new_params, new_opt, cs, skipped, mstates
                # mstates = (pre-cycle buffers, post-forward buffers): the
                # grad-only programs committed model_state eagerly, so the
                # skip branch must also hand the PRE-cycle buffers back —
                # a poisoned forward's BN stats die with the skipped update
                mstate0, mstate_now = mstates
                ok = guard_lib.tree_all_finite(grads)
                return jax.lax.cond(
                    ok,
                    lambda: apply_all()
                    + (guard_lib.reset_consecutive(skipped), mstate_now),
                    lambda: (params, opt_state, comm_state,
                             guard_lib.bump_skip_counters(skipped), mstate0),
                )

            self._update = jax.jit(apply, donate_argnums=(0, 1, 2, 3))
        return self._update

    def _comm_hook_name(self) -> str:
        return getattr(self.model.accelerator, "comm_hook", "none")

    def _poison_if_donated(self):
        """After a failed dispatch that may have donated the model/optimizer
        buffers: poison the model so reads raise the clear restore-from-
        checkpoint error, not JAX's obscure 'Array has been deleted'."""
        model = self.model
        leaves = jax.tree_util.tree_leaves(
            (model._params, model._model_state, self.opt_state,
             self._comm_state, self._skipped)
        )
        if any(getattr(l, "is_deleted", lambda: False)() for l in leaves):
            model._params = model._model_state = _LOST_TO_FAILED_FLUSH
            self.opt_state = None
            self._comm_state = None
            self._skipped = None

    def skip_counters(self):
        """Host ``(total, consecutive)`` of the guard's skipped-update
        counters; ``(0, 0)`` when the guard is off or nothing has stepped.
        One tiny fetch — call per epoch, not per step."""
        if self._skipped is None:
            return 0, 0
        t, c = jax.device_get(
            (self._skipped["total"], self._skipped["consecutive"])
        )
        return int(t), int(c)

    def _run_fused(self, xb, yb, wb, criterion, step_idx, lazy_loss):
        """forward + backward + optimizer update as ONE jit dispatch (the
        managed analog of the native compiled train step)."""
        model = self.model
        fn = model._get_fused_step(criterion, self.optimizer)
        try:
            loss, new_params, new_mstate, new_opt, new_comm, new_skipped = fn(
                model._params, model._model_state, self.opt_state,
                self._comm_state, self._skipped, model._bwd_key, step_idx,
                xb, yb, wb,
            )
        except BaseException:
            self._poison_if_donated()
            raise
        model._params, model._model_state = new_params, new_mstate
        self.opt_state = new_opt
        self._comm_state = new_comm
        self._skipped = new_skipped
        lazy_loss._value = loss

    def flush(self):
        """Run all queued steps now. K >= 2 entries run as one lax.scan
        program (compiled once per distinct K; the per-epoch remainder reuses
        the single-step program entry by entry)."""
        queue, self._queue = self._queue, []
        if not queue:
            return
        try:
            self._dispatch_flush(queue)
        except BaseException:
            # The dispatch failed (compile OOM, runtime disconnect): the
            # queued updates are lost and donated buffers may be gone. Make
            # every still-unresolved loss read fail loudly rather than
            # silently recompute a forward against un-updated params.
            model = self.model
            for entry in queue:
                lazy_loss = entry[5]
                lazy_loss._queued_on = None
                if lazy_loss._value is None and lazy_loss._value_src is None:
                    lazy_loss._dropped = True
                    lazy_loss._drop_reason = (
                        "its fused-step dispatch failed (see the original "
                        "exception)"
                    )
            # Donation only happens if execution started; a trace/compile
            # failure leaves the buffers valid.
            self._poison_if_donated()
            raise

    def _dispatch_flush(self, queue):
        model = self.model
        if len(queue) == 1:
            xb, yb, wb, criterion, step_idx, lazy_loss = queue[0]
            self._run_fused(xb, yb, wb, criterion, step_idx, lazy_loss)
            lazy_loss._queued_on = None
            return
        # Any multi-step queue — full, epoch remainder, or an early-read
        # partial — dispatches as ONE scan. Scan programs are cached per
        # length, and the lengths that occur recur (the full depth every
        # cycle, the same remainder every epoch), so each compiles once per
        # run; an epoch SHORTER than the fusion depth still gets exactly one
        # dispatch per epoch instead of silently degrading to per-step.
        criterion = queue[0][3]
        fn = model._get_fused_scan_step(criterion, self.optimizer, len(queue))
        idxs = jnp.asarray([e[4] for e in queue], jnp.int32)
        xs = tuple(e[0] for e in queue)
        ys = tuple(e[1] for e in queue)
        ws = tuple(e[2] for e in queue)
        new_params, new_mstate, new_opt, new_comm, new_skipped, losses = fn(
            model._params, model._model_state, self.opt_state,
            self._comm_state, self._skipped, model._bwd_key, idxs, xs, ys, ws,
        )
        model._params, model._model_state = new_params, new_mstate
        self.opt_state = new_opt
        self._comm_state = new_comm
        self._skipped = new_skipped
        for i, entry in enumerate(queue):
            lazy_loss = entry[5]
            lazy_loss._value_src = (losses, i)
            lazy_loss._queued_on = None


class Accelerator:
    """Managed entry to the tpuddp backend. Topology comes from the live JAX
    runtime (the analog of HF accelerate reading torchrun env vars)."""

    def __init__(
        self,
        mesh=None,
        seed: Optional[int] = None,
        fuse_steps: int = 1,
        num_chips: Optional[int] = None,
        clip_grad_norm: Optional[float] = None,
        gradient_accumulation_steps: int = 1,
        weight_update_sharding: bool = False,
        comm_hook: str = "none",
        bucket_cap_mb: float = comm_lib.DEFAULT_BUCKET_CAP_MB,
        comm_topology: str = "flat",
        topk_density: float = comm_lib.DEFAULT_TOPK_DENSITY,
        guard=None,
        augment=None,
    ):
        """``fuse_steps``: K > 1 batches per-step calls into one compiled
        lax.scan dispatch (the managed analog of the native scan fusion) —
        loss values then materialize at flush time, so pair it with deferred
        metric reading (collect the LazyLoss objects; read at epoch end).
        ``"auto"`` resolves at each optimizer's first step to 32 (the
        BASELINE-measured managed depth — the r5 full-bench managed-AlexNet
        row ran fuse=32 within ~3% of the native K-fused step), capped by a
        ~256 MB queued-batch staging budget computed from the actual batch's
        bytes (large inputs resolve shallower; e.g. 128x224x224x3 bf16
        batches cap at 6). The native ``scan_steps: auto`` analog goes
        deeper (64, same budget) because the native scan stages one
        super-batch instead of paying per-batch sharded placement.

        ``num_chips``: restrict the data mesh to the first N local devices
        (the managed analog of ``local.tpu.num_chips`` — without it a
        configured sub-world would be silently ignored on multi-chip hosts).
        Ignored when an explicit ``mesh`` is passed.

        ``weight_update_sharding``: ZeRO-1 on the managed path — Adam moments
        live as a flat vector SHARDED over the data axis and each chip
        computes only its parameter shard's update (XLA lowers the exchange
        to reduce-scatter + all-gather via sharding constraints; see
        :class:`_FlatShardedUpdate` and arxiv.org/abs/2004.13336).

        ``comm_hook``: gradient-communication hook ("none" | "bf16" |
        "bf16_ef"), the managed-path analog of torch DDP's comm hooks
        (parallel/comm.py). On this path XLA's partitioner inserts the
        cross-replica psum inside backward, so the hook quantizes the
        aggregated gradient through the wire dtype — with bf16_ef's
        persistent error-feedback residual (round-tripped by
        save_state/load_state) — preserving the hooks' convergence contract;
        the genuine on-the-wire byte reduction is the explicit
        (DistributedDataParallel, shard_map) path's property.
        ``bucket_cap_mb`` is accepted for knob parity (bucketing is a
        collective-granularity construct of the explicit path).

        ``guard``: the numerical guard (resilience/guard.py; same knob as
        ``DistributedDataParallel``): the fused/scan/accumulation update
        programs gate the optimizer tail behind a finiteness check on the
        XLA-aggregated f32 gradient (checked before the comm hook
        quantizes), a poisoned step is a bitwise no-op counted in the
        optimizer's skip counters (``PreparedOptimizer.skip_counters()``,
        round-tripped by save_state/load_state), and ``prepare`` audits
        every replica's parameter copy. Off by default — identical
        programs.

        ``augment``: on-device train augmentation ``(rng, x) -> x`` folded
        INTO the compiled step programs (the async pipeline's managed-path
        analog of the native ``DistributedDataParallel(augment=...)``):
        ``model(raw_inputs)`` then takes decoded uint8 batches and the
        normalize/flip/resize runs inside the same dispatch as forward+
        backward+update — one dispatch per step, host workers only decode
        and stack. The augment key derives from the step rng by a constant
        fold (``_AUG_FOLD``), so the model's own rng stream (dropout) is
        unchanged by folding. Train-grad programs only; eval paths keep
        their explicit transform. None (default): inputs are used as
        given — the legacy separate-augment cadence."""
        self.mesh = mesh if mesh is not None else data_mesh(num_chips)
        key, _ = seeding.set_seed_based_on_rank(base_seed=seed)
        self._key = key
        self._models = []
        if fuse_steps in (None, "auto"):
            self.fuse_steps = "auto"
        else:
            self.fuse_steps = max(1, int(fuse_steps))
        # clip the GLOBAL-batch gradient (already cross-replica aggregated
        # under jit) before the update — clip-after-aggregate semantics,
        # same as the native path's clip_grad_norm
        self.clip_grad_norm = (
            float(clip_grad_norm) if clip_grad_norm is not None else None
        )
        # HF-parity gradient accumulation: optimizer.step() accumulates the
        # global-batch gradient and applies ONE averaged update every N
        # steps (zero_grad stays safe to call every batch, as HF's managed
        # no-op semantics allow; the boundary step clears the accumulator).
        self.gradient_accumulation_steps = max(1, int(gradient_accumulation_steps))
        self.weight_update_sharding = bool(weight_update_sharding)
        self.comm_hook = comm_lib.validate_hook(comm_hook)
        # comm_topology is accepted for config parity with the explicit API,
        # but only "flat" is implementable here: the managed path's gradient
        # collective is inserted by XLA's partitioner, so there is no seam to
        # express the intra-host/inter-host hop split through. The knob
        # refuses rather than silently running flat under a hierarchical
        # label — the byte accounting must never claim a topology that did
        # not reach the wire.
        comm_lib.validate_topology(comm_topology)
        if comm_topology != "flat":
            raise ValueError(
                "comm_topology='hierarchical' needs the explicit API "
                "(DistributedDataParallel / train_native.py, mode="
                "'shard_map'): the managed path's collective is XLA-"
                "inserted and cannot be hop-split"
            )
        self.comm_topology = comm_topology
        self.topk_density = float(topk_density)
        comm_lib.bucket_topk(1, self.topk_density)  # range-validate eagerly
        self.guard = guard_lib.resolve_guard(guard)
        # run_meta's comm.overlap (schema v10): the managed path's exchange is
        # XLA's own psum behind the backward pass, as the explicit path's is
        self.comm_overlap_meta = dict(comm_lib.OVERLAP_META)
        self.augment = augment
        # typed event dicts from the last load_state's elastic reshard (a
        # topology_change when the restored state was written on a different
        # world size); the managed entrypoint lands them in history.jsonl
        self.last_restore_events: list = []
        self.bucket_cap_mb = float(bucket_cap_mb)
        if self.bucket_cap_mb <= 0:
            # same knob contract as DistributedDataParallel: a config that
            # validates against one API must not crash the other
            raise ValueError(f"bucket_cap_mb must be > 0, got {bucket_cap_mb!r}")
        if self.gradient_accumulation_steps > 1:
            if self.fuse_steps == "auto":
                # accumulation owns the step cadence; auto-fusion yields
                self.fuse_steps = 1
            elif self.fuse_steps > 1:
                raise ValueError(
                    "gradient_accumulation_steps and fuse_steps are mutually "
                    "exclusive (fused scan steps each apply an update)"
                )

    # -- topology (HF property-name parity) --
    @property
    def device(self):
        return self.mesh.devices.flat[0]

    @property
    def num_processes(self) -> int:
        return jax.process_count()

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def local_process_index(self) -> int:
        return jax.process_index()

    @property
    def is_main_process(self) -> bool:
        return jax.process_index() == 0

    @property
    def is_local_main_process(self) -> bool:
        return jax.process_index() == 0

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def next_rng_key(self):
        """A fresh PRNG key from the accelerator's per-process stream (for
        host-driven augmentation in the managed path)."""
        return self._next_key()

    # -- the core verbs --
    def prepare(self, *objects):
        """Wrap (model, optimizer, dataloader) for distributed execution —
        reference usage at multi-GPU-training-accelerate.py:129-131. DataLoaders
        are re-created sharded (each process loads only its replicas' shard;
        batch_size stays per-replica, matching HF semantics and the README's
        memory caveat, README.md:72-73). Objects deliberately NOT prepared
        (the reference's test_loader) keep their full unsharded stream."""
        out = []
        model_ctx: Optional[PreparedModel] = None
        for obj in objects:
            if isinstance(obj, Module):
                model_ctx = PreparedModel(self, obj)
                self._models.append(model_ctx)
                out.append(model_ctx)
            elif isinstance(obj, PreparedModel):
                model_ctx = obj
                out.append(obj)
            elif isinstance(obj, optim_lib.Optimizer):
                out.append(("optimizer", obj))
            elif isinstance(obj, (DataLoader, ShardedDataLoader)):
                out.append(obj)
            else:
                raise TypeError(f"cannot prepare object of type {type(obj)!r}")
        # bind optimizers to the model prepared in the same call
        for i, obj in enumerate(out):
            if isinstance(obj, tuple) and obj[0] == "optimizer":
                if model_ctx is None:
                    raise ValueError("prepare() got an optimizer but no model")
                out[i] = PreparedOptimizer(obj[1], model_ctx)
                model_ctx._optimizer = out[i]  # for load_model's reset
        # A user-supplied sampler's order is PRESERVED: the sharded loader
        # pads it by wrap and strides it across replicas (HF semantics — a
        # custom sampler rides inside the sharded batch sampler; it is never
        # silently replaced with a reshuffle).
        out = [
            ShardedDataLoader(
                o.dataset, o.batch_size, self.mesh,
                shuffle=o.shuffle,
                seed=o.seed,
                drop_last=o.drop_last,
                sampler=o.sampler,
            )
            if isinstance(o, DataLoader)
            else o
            for o in out
        ]
        return out[0] if len(out) == 1 else tuple(out)

    def backward(self, loss: LazyLoss):
        """Fused forward+backward+grad-sync (reference :53's
        ``accelerator.backward(loss)``)."""
        if not isinstance(loss, LazyLoss):
            raise TypeError(
                "accelerator.backward expects the LazyLoss produced by a tpuddp "
                "criterion applied to a prepared model's outputs"
            )
        loss._run_backward()

    def wait_for_everyone(self):
        """Global barrier (reference :106)."""
        col.barrier("tpuddp_accelerate_wait")

    def save_model(self, model: PreparedModel, save_dir: str):
        """Single-writer save of the *unwrapped* weights (reference :108's
        ``accelerator.save_model`` contract): process 0 writes
        ``save_dir/model.npz``."""
        model._flush_queues()  # queued fused steps must land before the read
        if self.is_main_process:
            os.makedirs(save_dir, exist_ok=True)
            ckpt.save(
                os.path.join(save_dir, "model.npz"),
                {"params": model.params, "model_state": model.model_state},
            )
        col.barrier("tpuddp_accelerate_save")

    def load_model(self, model: PreparedModel, save_dir: str):
        """Restore the weights written by :meth:`save_model` into a prepared
        model (the managed resume path; the reference only documents loading,
        README.md:51-52). The model must have been initialized (one forward
        or a prior training step) so the checkpoint has a structure to load
        into."""
        # gradients/steps staged against the PRE-restore weights must not be
        # executed (a flush would) or applied on top of the restored ones
        self._discard_staged_work(model, "load_model discarded the staged step")
        if model._params is _LOST_TO_FAILED_FLUSH:
            raise RuntimeError(
                "this model's buffers were lost to a failed fused dispatch; "
                "re-prepare it (accelerator.prepare) and run one forward, "
                "then load_model"
            )
        if model._params is None:
            raise RuntimeError(
                "load_model needs an initialized model: run one forward "
                "(model(x)) first so the parameter structure exists"
            )
        restored = ckpt.load(
            os.path.join(save_dir, "model.npz"),
            {"params": model._params, "model_state": model._model_state},
        )
        model._params, model._model_state = replicate(
            self.mesh, (restored["params"], restored["model_state"])
        )
        opt = getattr(model, "_optimizer", None)
        if opt is not None:
            # Adam moments computed against the PRE-restore weights must not
            # steer updates to the restored ones; this is a weights-only
            # restore, so the moments re-init to zero on the next step.
            # load_state restores them losslessly. The comm-hook residual is
            # pre-restore compression error — it resets with them.
            opt.opt_state = None
            opt._comm_state = None
        return model

    @staticmethod
    def _discard_staged_work(model: PreparedModel, reason: str):
        """Drop anything staged against the CURRENT (about-to-be-replaced)
        weights — the pending backward, queued fused steps, and a partial
        accumulation cycle — so a restore never executes or applies them.
        Must run BEFORE any flush: a flush would *execute* the queued steps
        against the pre-restore weights, a wasted dispatch whose updates the
        restore immediately overwrites."""
        if model._pending is not None:
            old = model._pending[-1]
            if old._value is None:
                old._dropped = True
                old._drop_reason = reason
        model._pending = None
        model._pending_grads = None
        opt = getattr(model, "_optimizer", None)
        if opt is not None:
            for entry in opt._queue:
                entry[5]._queued_on = None
                entry[5]._dropped = True
                entry[5]._drop_reason = reason
            opt._queue = []
            opt._accum_grads = None
            opt._accum_count = 0
            opt._cycle_mstate = None

    def _full_state_like(self, model: PreparedModel, optimizer: "PreparedOptimizer"):
        """Template tree for the lossless managed state: weights + buffers +
        optimizer moments + the RNG stream position (accelerator key, backward
        base key, backward counter)."""
        # zeros template so a never-stepped (or weights-only-restored) run
        # still has the structure to save/load into; under
        # weight_update_sharding this also establishes the flat sharded layout
        optimizer._ensure_opt_state()
        tree = {
            "params": model._params,
            "model_state": model._model_state,
            "opt_state": optimizer.opt_state,
            "rng_key": self._key,
            "bwd_key": model._bwd_key,
            "bwd_counter": np.asarray(model._bwd_counter, np.int64),
        }
        if optimizer._comm_state is not None:
            # comm_hook="bf16_ef": the error-feedback residual is training
            # state — dropping it on resume would re-bias the first steps
            # after restore. Only present when the hook carries state, so
            # hook-less checkpoints keep their historical structure.
            tree["comm_state"] = optimizer._comm_state
        if optimizer._skipped is not None:
            # guard skip counters round-trip like the residual: the rollback
            # policy's consecutive-run must survive a resume
            tree["skipped_steps"] = optimizer._skipped
        return tree

    def save_state(
        self,
        model: PreparedModel,
        optimizer: "PreparedOptimizer",
        save_dir: str,
        epoch: int = 0,
        step: Optional[int] = None,
        cursor: Optional[dict] = None,
    ):
        """Lossless full-training-state save — the HF ``save_state`` analog
        (``save_model`` keeps the reference's weights-only contract,
        multi-GPU-training-accelerate.py:104-108; this adds what a restart
        needs): process 0 writes ``save_dir/state_{epoch}.npz`` holding
        params, model buffers, optimizer moments, and the RNG stream
        position, so :meth:`load_state` resumes bit-for-bit.

        ``step``/``cursor`` write a STEP-granular snapshot instead
        (``state_{epoch}_s{step}.npz`` with the v4 data cursor): the
        mid-epoch drain path — :meth:`load_state` then resumes AT that
        step with zero batches replayed."""
        model._flush_queues()  # queued fused steps are committed updates
        model._check_not_lost()
        if model._params is None:
            raise RuntimeError(
                "save_state needs an initialized model: run one forward or a "
                "training step first"
            )
        if optimizer._accum_count:
            raise RuntimeError(
                "save_state mid-gradient-accumulation-cycle would silently "
                "lose the partial cycle; call optimizer.flush_accumulation() "
                "first (the entrypoint's epoch boundary does)"
            )
        tree = self._full_state_like(model, optimizer)
        cursor_acc = None
        if cursor is not None:
            cursor = dict(cursor)
            cursor.setdefault("version", ckpt.FORMAT_VERSION)
            cursor.setdefault("epoch", int(epoch))
            if step is not None:
                cursor.setdefault("step", int(step))
            cursor_acc = cursor.pop("acc", None)
        # one writer discipline for every checkpoint flavor: cross-host
        # gather (collective) -> process-0 write -> barrier; world_size
        # stamps the v2 topology record so the state can reshard elastically
        ckpt.save_on_main(
            save_dir, epoch, tree, prefix="state",
            world_size=int(self.mesh.devices.size),
            step=step, cursor=cursor, cursor_acc=cursor_acc,
        )

    def load_state(
        self, model: PreparedModel, optimizer: "PreparedOptimizer", save_dir: str
    ) -> int:
        """Restore the newest ``state_{epoch}.npz`` written by
        :meth:`save_state` (the managed resume path). Returns the next epoch
        to train (0 when no state file exists — fresh start). The model must
        be initialized (one forward, even a lazy un-materialized one,
        suffices) so the structure to load into exists.

        A step-granular snapshot (``state_{epoch}_s{step}.npz``, written by
        a mid-epoch drain) restores too: its v4 data cursor lands in
        ``self.last_restore_cursor`` and the return value is the cursor's
        OWN epoch — the driver continues that epoch at the cursor step with
        zero batches replayed. ``last_restore_cursor`` is None after an
        epoch-granular restore."""
        self.last_restore_cursor = None
        found = ckpt.latest(save_dir, prefix="state")
        if found is None:
            # fresh start: a no-op call must not touch in-flight work
            return 0
        # discard (don't execute) anything staged against pre-restore weights
        self._discard_staged_work(model, "load_state discarded the staged step")
        if model._params is _LOST_TO_FAILED_FLUSH:
            raise RuntimeError(
                "this model's buffers were lost to a failed fused dispatch; "
                "re-prepare it (accelerator.prepare) and run one forward, "
                "then load_state"
            )
        if model._params is None:
            raise RuntimeError(
                "load_state needs an initialized model: run one forward "
                "(model(x)) first so the parameter structure exists"
            )
        like = self._full_state_like(model, optimizer)
        path, epoch = found
        # elastic resume: a state written on a different world size reshards
        # onto THIS mesh (weight-update-sharded flat moments re-pad; the
        # managed EF residual is a tree of parameter-shaped leaves, already
        # world-independent). The reshard surfaces as typed event dicts in
        # `last_restore_events` (the SAME construction the native driver
        # uses) for the entrypoint to land in history.jsonl once the
        # run_meta header exists.
        world = int(self.mesh.devices.size)
        actions: list = []
        restored, topo = ckpt.load_with_topology(
            path, like, world_size=world, reshard_actions=actions
        )
        self.last_restore_events = ckpt.build_reshard_events(
            path, epoch, topo, world, actions
        )
        cursor = ckpt.read_cursor(path)
        if cursor is not None and actions:
            # a resharded restore changed the data order the cursor's plan
            # key describes — poison it so the driver redoes the epoch
            # instead of resuming a plan that no longer exists
            cursor["plan_key"] = None
        meta = ckpt.read_meta(path)
        if cursor is not None:
            self.last_restore_cursor = cursor
            next_epoch = int(cursor.get("epoch", epoch))
        elif not meta.get("completed", 1):
            # legacy emergency save (no cursor): redo the interrupted epoch
            next_epoch = epoch
        else:
            next_epoch = epoch + 1
        model._params, model._model_state = replicate(
            self.mesh, (restored["params"], restored["model_state"])
        )
        if isinstance(optimizer.optimizer, _FlatShardedUpdate):
            # flat sharded layout: moments go back SHARDED, not replicated
            optimizer.opt_state = optimizer.optimizer.place_state(
                restored["opt_state"]
            )
        else:
            optimizer.opt_state = replicate(self.mesh, restored["opt_state"])
        if "comm_state" in restored:
            optimizer._comm_state = replicate(self.mesh, restored["comm_state"])
        if "skipped_steps" in restored:
            optimizer._skipped = replicate(self.mesh, restored["skipped_steps"])
        self._key = restored["rng_key"]
        model._bwd_key = restored["bwd_key"]
        model._bwd_counter = int(restored["bwd_counter"])
        return next_epoch

    def gather(self, x):
        """Concatenate a data-sharded array's shards onto every host."""
        from jax.experimental import multihost_utils

        if jax.process_count() > 1:
            return multihost_utils.process_allgather(x)
        return np.asarray(x)

    def print(self, *args, **kwargs):
        if self.is_local_main_process:
            print(*args, **kwargs)
