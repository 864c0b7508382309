"""Module protocol: pure functional layers with explicit parameter pytrees.

A ``Module`` is a hyperparameter container with two methods:

- ``init(key, x) -> (params, state)``    — create parameters from an input
  *shape* (``x`` may be a concrete array or a ``jax.ShapeDtypeStruct``);
- ``apply(params, state, x, ctx) -> (y, new_state)`` — the forward pass.
  ``state`` carries non-trainable buffers (BatchNorm running stats); layers
  without buffers use ``()`` and return it unchanged.

``ctx`` (:class:`Context`) threads the dynamic bits: ``train`` flag, a PRNG
key for stochastic layers, and the mesh ``axis_name`` for cross-replica
statistic sync (the SyncBatchNorm contract). It is constructed inside the
jitted step function, so ``rng`` may be a tracer while ``train``/``axis_name``
stay static.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax

from tpuddp.observability import profiling as _prof


class Context:
    """Dynamic forward-pass context.

    ``sample_weight``: optional per-sample 0/1 mask aligned with batch axis 0
    (the static-shape padding convention, tpuddp/data/loader.py) so that
    batch-statistic layers (BatchNorm) can exclude padded rows — padding must
    not bias running statistics (torch feeds a ragged last batch instead)."""

    __slots__ = ("train", "rng", "axis_name", "sample_weight")

    def __init__(
        self,
        train: bool = False,
        rng=None,
        axis_name: Optional[str] = None,
        sample_weight=None,
    ):
        self.train = train
        self.rng = rng
        self.axis_name = axis_name
        self.sample_weight = sample_weight

    def child(self, i: int) -> "Context":
        """Context for the i-th submodule: fold the index into the key so each
        stochastic layer draws independently."""
        rng = None if self.rng is None else jax.random.fold_in(self.rng, i)
        return Context(self.train, rng, self.axis_name, self.sample_weight)


def _sds(x) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


class Module:
    """Base class. Subclasses implement ``init`` and ``apply``."""

    def init(self, key, x) -> Tuple[Any, Any]:
        return (), ()

    def apply(self, params, state, x, ctx: Context):
        raise NotImplementedError

    def init_with_output_shape(self, key, x):
        """init + the output ShapeDtypeStruct (no FLOPs: uses eval_shape)."""
        params, state = self.init(key, x)
        out = jax.eval_shape(
            lambda p, s, v: self.apply(p, s, v, Context(train=False))[0],
            params,
            state,
            _sds(x),
        )
        return params, state, out

    # Iteration hook so tree-walking utilities (convert_sync_batchnorm) work.
    def children(self):
        return ()

    def divergent_state(self) -> Optional[bool]:
        """Whether THIS module's own buffers can diverge across replicas under
        data parallelism (per-replica batch statistics, counters, ...) — the
        protocol behind ``sync_buffers="none"`` validation
        (tpuddp/nn/norm.py:has_divergent_buffers).

        Three-valued by design so the validation holds BY CONSTRUCTION:

        The declaration covers the module's OWN buffers only — children are
        always walked separately by the checker:

        - ``True``  — diverges (BatchNorm with unsynced running stats);
        - ``False`` — the module vouches its own state is replica-invariant
          (or that it has none beyond its children's); variable-creating
          modules must declare this explicitly (Linear, Conv2d, Sequential,
          BasicBlock do);
        - ``None``  (this default) — undeclared. Any module that creates
          variables (overrides ``init``) but never declared its divergence is
          treated as divergent: a future stateful layer cannot silently slip
          past ``sync_buffers="none"`` validation by being forgotten.
          Modules that don't override ``init`` are stateless by construction.
        """
        return None


class Sequential(Module):
    """Composes modules in order; params/state are tuples over children."""

    def __init__(self, *layers: Module):
        if len(layers) == 1 and isinstance(layers[0], (list, tuple)):
            layers = tuple(layers[0])
        self.layers = tuple(layers)

    def init(self, key, x):
        params, states = [], []
        x = _sds(x)
        for i, layer in enumerate(self.layers):
            p, s, x = layer.init_with_output_shape(jax.random.fold_in(key, i), x)
            params.append(p)
            states.append(s)
        return tuple(params), tuple(states)

    def apply(self, params, state, x, ctx: Context):
        new_states = []
        for i, layer in enumerate(self.layers):
            with _prof.scope(_prof.layer_scope(i, layer)):
                x, s = layer.apply(params[i], state[i], x, ctx.child(i))
            new_states.append(s)
        return x, tuple(new_states)

    def children(self):
        return self.layers

    def divergent_state(self) -> bool:
        return False  # composes children only; owns no buffers of its own

    def __getitem__(self, i):
        return self.layers[i]

    def __len__(self):
        return len(self.layers)
