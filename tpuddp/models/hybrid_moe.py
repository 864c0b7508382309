"""Hybrid mixture-of-experts language models on one decoder trunk, as one chip
of an expert-parallel job holds them: layers whose mixers differ in kind
(linear attention, softmax attention over every earlier key, over a sliding
window or over the keys a learned indexer chose, a gated short convolution),
whose feed-forward is sparse or dense,
whose head is the embedding's transpose or a matrix of its own, and which may
carry state beside their parameters (a router's selection bias).

Every layer is ``h = x + Mixer(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))``;
where its tree has ``mixer_out_norm`` and ``ff_out_norm`` (``sandwich_norms``)
a second norm stands on each half's result before the add.
``layer_types`` lists each layer's type, which names its mixer and its scope
(``<i>_<type>``), seven of them:

- ``GatedDeltaNet`` (``nn/deltanet.py``) and ``GatedAttention`` (softmax
  attention with a per-head norm on queries and keys, rotary on part of the
  head and a sigmoid output gate): the Qwen3-Next family, whose pattern
  ``full_attention_interval`` gives where no list is given (layer ``i`` is
  gated attention where ``(i + 1) % full_attention_interval == 0``), with
  zero-centred RMSNorms (scale ``1 + w``) and one shared expert behind a
  sigmoid gate beside the routed ones.
- ``SlidingAttention`` and ``FullAttention``: the Mellum 2 family's two
  layers, plain grouped-query softmax attention with the per-head norm (where
  the mixer's tree has ``q_norm``: ``qk_norm``) and
  rotary on the whole head, no gate; the first sees ``sliding_window`` keys
  (a query's own and those before it) under the plain rotary table, the
  second every earlier key under a YaRN-scaled table (``yarn``; none: the
  plain table). Plain RMSNorms (scale ``w``, from 1) and no shared expert
  (``shared_width=0``).
- ``ShortConv``: the LFM2 family's operator beside its ``FullAttention``
  layers. One projection to three streams ``b | c | u``, a depthwise causal
  convolution of ``conv_kernel`` taps over ``b * u`` and the gate ``c`` after
  it (``seq.gated_short_conv``), one projection back: no activation, no
  norm, no scan.
- ``LatentAttention``: the DeepSeek-V2 family's attention as GLM-4.7-Flash has
  it, in the decompressed form training runs. Queries through a
  ``q_lora_rank`` latent, keys and values through a ``kv_lora_rank`` one, a
  norm inside each low-rank pair; ``n_heads`` ungrouped heads whose queries
  and keys are ``qk_nope_dim`` without position beside ``qk_rope_dim`` under
  rotary, the rotary key ONE a token that every head shares; values
  ``v_head_dim`` wide (``qk_nope_dim + qk_rope_dim``: scores and values one
  width, so ``seq.causal_attention`` takes the heads as any others). Scopes
  ``q_latent``, ``kv_latent``, ``attention``, ``o_proj``.
- ``SparseAttention``: learned sparse attention as Keye-VL-2.0's language
  model has it (DeepSeek Sparse Attention's indexer on grouped-query
  attention with the per-head norm and rotary on the whole head). Beside
  attention's own projections the mixer's tree holds an ``indexer``
  (``q_proj`` to ``index_heads`` heads of ``index_head_dim``, ``k_proj`` to
  ONE key head under a LayerNorm, ``w_proj`` to a weight a head), which reads
  the layer's normed input behind a stop-gradient and scores every earlier
  key, ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``; a query attends
  only the ``index_top_k`` keys of largest score (all of them while it has
  that many or fewer; exact, ties to the earlier key), the same set for every
  head: the mask is data, decided in the step. The selection cannot be
  differentiated, so the language model's loss never reaches the indexer: it
  learns from an objective of its own inside the layer, the KL divergence of
  the heads' mean attention distribution (behind a second stop-gradient) to
  ``softmax_{S_t}(I)``, a mean over rows, summed over layers, which enters
  the gradient at ``indexer_loss_weight`` beside the routers' auxiliary loss
  and reaches the indexer's leaves alone; ``nn/sequence.py``'s
  ``SPARSE_COUNTERS`` carry it out. The layer is its own checkpoint: queries
  go a group of rows at a time with everything that is rows by keys, each
  group recomputed in the backward pass. Scopes ``qkv``, ``index_proj``,
  ``index_scores``, ``index_select``, ``attention``, ``indexer_loss``,
  ``o_proj``. The family's dense warm-up stage (the model frozen, the indexer
  alone trained under dense attention) needs a mask of trainable leaves and
  is not built.

A layer's feed-forward is what its tree holds. ``moe``: a router over all
``n_experts``, ``top_k`` of them a token (their renormalised weights times
``routed_scale``), of which this chip computes the ``experts_held`` it holds
(``nn/moe.py``), and a shared expert where ``shared_width`` is not 0, behind
a sigmoid gate or, without ``shared_gate``, added as it is; with
``expert_bias`` the router
scores by sigmoid and chooses by score plus a per-expert bias, which is the
model's state (a tuple a layer; no gradient reaches it): after each training
step ``nn/moe.py:balanced_bias`` moves it by ``bias_update_rate`` towards an
even load, from the step's counts over all experts, summed over the data
axis. ``mlp``: a dense SwiGLU of ``dense_width``, the first ``dense_layers``
layers', ``mlp_chunk`` tokens at a time. The embedding covers the
``num_classes`` rows of the vocabulary that this chip holds; the head is a
leaf of its own or, with ``tied_head``, the embedding transposed (no ``head``
leaf: the one leaf's gradient is the sum of both uses). Every projection,
the router and the head are drawn at ``init_std``; so is the embedding unless
``embed_std`` gives it a scale of its own (at 0.02 beside unit-normed layer
inputs a fresh model whose first mixer is full attention adds one common
vector, the uniform softmax's mean value, several times the embedding's size
to every position, and every router downstream then sees the same token:
PERF.md, PR 44).

A layer runs once a token unless ``loop_steps`` is more than 1: then the whole
stack (dense layers, no state) is walked that many times over the SAME leaves
in one rolled loop, so a leaf's gradient is the sum over its uses and the
program holds the stack once; the state is normed after every pass
(``final_norm``, shared) and that normed state is what the next pass takes in.
With an ``exit_gate`` leaf every pass's normed state is an exit: training
returns :class:`~tpuddp.nn.sequence.DeferredExits` (one head for all exits, a
token's loss the exits' losses weighted by the gate's distribution, its
entropy in the gradient alone; the exits' counters in place of an expert
layer's) and evaluation the last pass's logits. Without the leaf the last
pass's state alone goes to the head. In training a pass keeps its input and
is walked again in the backward pass (``_pass``).

With ``next_token_modules`` 1 a module stands after the stack (multi-token
prediction at depth 1, arXiv:2412.19437 section 2.2; leaf ``mtp``): position
``i``'s trunk state before the final norm and the embedding of token ``i + 1``,
each normed, joined and projected back to the model's width, go through one
more layer of the last layer's type with a sparse feed-forward of its own (its
selection bias is the state's last entry, its counts add to the expert
counters), then a norm of the module's own; embedding and head are the
model's. Training returns these states beside the trunk's in the one
:class:`~tpuddp.nn.sequence.DeferredLogits` (the second head's loss times
``next_token_loss_weight`` in the gradient alone, ``mtp_loss_sum`` and
``mtp_tokens`` among the counters); evaluation does not run the module. Scope
``mtp`` (``mtp/proj``, then the layer's own scopes) under ``tpuddp.forward``
and ``mtp`` round the second head under ``tpuddp.loss``.

The training forward returns :class:`~tpuddp.nn.sequence.DeferredLogits`
(the criterion takes the loss from the hidden states in chunks); evaluation
returns ``(B, T, V)`` float32 logits. Each layer's mixer and feed-forward are
recomputed in the backward pass of a training step. Inside a layer every
loop is rolled (sequences, the DeltaNet chunks and the rows of its inverse,
the expert rounds, the dense feed-forward's and the loss's chunks, and
attention's query blocks where
attention runs blockwise: ``nn/sequence.py`` lowers it to one fused kernel a
sequence on a TPU at the published widths, and to rolled query blocks on the
CPU, at the tiny presets and under ``mode="auto"``; ``nn/deltanet.py`` lowers
what a DeltaNet chunk computes alone (the inverse's rows among it) and the short
convolution before the scan to fused kernel pairs under the same conditions); the layers are a Python loop, because
their parameters are one tree a layer, as the published checkpoints have
them, and each layer keeps its own scope name.

Registry names (``models/__init__.py``): ``qwen3_next_ep16``, the published
widths of Qwen3-Next-80B-A3B as share 0 of 16 chips that divide each layer's
experts (one period of four layers); ``mellum2_ep4``, those of
Mellum2-12B-A2.5B as share 0 of 4 (one period: three sliding layers and a
full one); ``lfm2_ep4``, those of LFM2-8B-A1B as share 0 of 4 (a leading
dense layer and one period: a full-attention layer and three convolution
layers); ``ouro_2_6b_l6``, those of Ouro-2.6B with every layer whole on the
chip (six dense full-attention layers with sandwich norms and no per-head
norm, walked four times, every pass an exit); ``glm_4_7_flash_ep8``, those of
GLM-4.7-Flash as share 0 of 8 (the dense leading layer and four sparse
latent-attention layers, the prediction module); ``keye_vl_2_0_ep8``, those of
Keye-VL-2.0-30B-A3B's language model as share 0 of 8 (five sparse-attention
layers); ``qwen3_next_tiny``, ``mellum2_tiny``, ``lfm2_tiny``, ``ouro_tiny``,
``glm_4_7_flash_tiny`` and ``keye_vl_2_0_tiny`` for the CPU tests.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from tpuddp.nn import deltanet, moe
from tpuddp.nn import sequence as seq
from tpuddp.nn.core import Context, Module
from tpuddp.observability import profiling as _prof

DELTANET, ATTENTION = "GatedDeltaNet", "GatedAttention"
SLIDING, FULL = "SlidingAttention", "FullAttention"
SHORT_CONV = "ShortConv"
LATENT = "LatentAttention"
SPARSE = "SparseAttention"
LAYER_TYPES = (DELTANET, ATTENTION, SLIDING, FULL, SHORT_CONV, LATENT, SPARSE)


class HybridMoELM(Module):
    """``num_classes`` is the number of vocabulary rows held (the zoo's
    ``load_model(name, num_classes)`` protocol)."""

    counter_names = moe.COUNTERS  # what the step carries out beside its metrics; a second head adds its own

    def __init__(
        self,
        num_classes: int,
        hidden_size: int = 2048,
        n_layers: int = 4,
        full_attention_interval: int = 4,
        layer_types=None,  # one of LAYER_TYPES a layer; None: the interval's pattern
        zero_centred_norms: bool = True,
        # softmax attention, all three types
        n_heads: int = 16,
        n_kv_heads: int = 2,
        head_dim: int = 256,
        partial_rotary_factor: float = 0.25,
        rope_theta: float = 1e7,
        sliding_window=None,  # keys a SlidingAttention query sees, its own among them
        yarn=None,  # FullAttention's scaling of the rotary table (seq.rotary_frequencies)
        # LatentAttention: n_heads heads whose queries and keys are a part without position beside a rotary part
        q_lora_rank: int = 768,
        kv_lora_rank: int = 512,
        qk_nope_dim: int = 192,
        qk_rope_dim: int = 64,
        v_head_dim: int = 256,
        # SparseAttention: an indexer of index_heads heads over one key head scores every earlier key, and a
        # query attends the index_top_k best
        index_heads: int = 16,
        index_head_dim: int = 64,
        index_top_k: int = 2048,
        indexer_loss_weight: float = 1.0,  # what the indexer's own objective weighs in the gradient
        # Gated DeltaNet; conv_kernel is the gated short convolution's too
        linear_k_heads: int = 16,
        linear_v_heads: int = 32,
        linear_k_dim: int = 128,
        linear_v_dim: int = 128,
        conv_kernel: int = 4,
        chunk: int = 64,
        # experts
        n_experts: int = 512,
        experts_held: int = 32,
        first_expert: int = 0,
        top_k: int = 10,
        expert_width: int = 512,
        shared_width: int = 512,
        shared_gate: bool = True,  # the shared expert stands behind a sigmoid gate
        routed_scale: float = 1.0,  # what a token's renormalised routed weights are multiplied by
        aux_loss_weight: float = 0.001,
        expert_bias: bool = False,  # a sigmoid router that chooses by score plus a bias (the model's state)
        expert_bias_std: float = 0.0,  # the bias as init draws it: 0, or a seeded normal of this scale
        bias_update_rate: float = 1e-3,
        # the first dense_layers layers' feed-forward: a SwiGLU of dense_width, no experts
        dense_layers: int = 0,
        dense_width: int = 0,
        tied_head: bool = False,  # the head is the embedding transposed
        # a stack that runs loop_steps times over the same leaves, the state normed after every pass
        loop_steps: int = 1,
        sandwich_norms: bool = False,  # a norm after the mixer and after the feed-forward too, before each residual add
        qk_norm: bool = True,  # softmax attention's per-head norm on queries and keys
        exit_gate: bool = False,  # every pass is an exit and a learned gate spreads a token's loss over them
        exit_entropy_weight: float = 0.05,  # what the exit distribution's entropy weighs in the gradient
        # a module after the stack that predicts the token after next: one more layer of the last layer's type
        # with a sparse feed-forward, between a projection of [state ; next token's embedding] and the shared head
        next_token_modules: int = 0,
        next_token_loss_weight: float = 0.3,  # what the second head's loss weighs in the gradient
        rms_eps: float = 1e-6,
        init_std: float = 0.02,
        embed_std=None,  # the embedding's own scale where it is not init_std's (an untied head keeps init_std)
        compute_dtype=jnp.float32,
        attention_q_block: int = 512,
        loss_chunk: int = 2048,
        mlp_chunk: int = 8192,
    ):
        if first_expert < 0 or first_expert + experts_held > n_experts:
            raise ValueError(
                f"experts {first_expert}..{first_expert + experts_held - 1} are not among {n_experts}"
            )
        if n_heads % n_kv_heads or linear_v_heads % linear_k_heads:
            raise ValueError("query/value heads must be a multiple of the key/value heads they share")
        self.vocab_size = int(num_classes)
        self.hidden_size, self.n_layers = int(hidden_size), int(n_layers)
        self.full_attention_interval = int(full_attention_interval)
        if layer_types is None:
            layer_types = [
                ATTENTION if (i + 1) % self.full_attention_interval == 0 else DELTANET
                for i in range(self.n_layers)
            ]
        self.layer_types = tuple(layer_types)
        if len(self.layer_types) != self.n_layers or not set(self.layer_types) <= set(LAYER_TYPES):
            raise ValueError(f"{self.n_layers} layers need a type each among {LAYER_TYPES}, not {layer_types}")
        if SLIDING in self.layer_types and not sliding_window:
            raise ValueError(f"a {SLIDING} layer needs its sliding_window")
        self.zero_centred_norms = bool(zero_centred_norms)
        self.sliding_window = None if sliding_window is None else int(sliding_window)
        self.yarn = None if yarn is None else dict(yarn)
        self.n_heads, self.n_kv_heads, self.head_dim = int(n_heads), int(n_kv_heads), int(head_dim)
        self.rotary_dim = int(head_dim * partial_rotary_factor)
        self.q_lora_rank, self.kv_lora_rank = int(q_lora_rank), int(kv_lora_rank)
        self.qk_nope_dim, self.qk_rope_dim, self.v_head_dim = int(qk_nope_dim), int(qk_rope_dim), int(v_head_dim)
        if LATENT in self.layer_types and self.qk_nope_dim + self.qk_rope_dim != self.v_head_dim:
            raise ValueError("a latent-attention head's scores and values are one width: qk_nope_dim + qk_rope_dim")
        self.index_heads, self.index_head_dim = int(index_heads), int(index_head_dim)
        self.index_top_k, self.indexer_loss_weight = int(index_top_k), float(indexer_loss_weight)
        self.rope_theta = float(rope_theta)
        self.linear_k_heads, self.linear_v_heads = int(linear_k_heads), int(linear_v_heads)
        self.linear_k_dim, self.linear_v_dim = int(linear_k_dim), int(linear_v_dim)
        self.conv_kernel, self.chunk = int(conv_kernel), int(chunk)
        self.n_experts, self.experts_held = int(n_experts), int(experts_held)
        self.first_expert, self.top_k = int(first_expert), int(top_k)
        self.expert_width, self.shared_width = int(expert_width), int(shared_width)
        self.shared_gate, self.routed_scale = bool(shared_gate), float(routed_scale)
        self.aux_loss_weight = float(aux_loss_weight)
        self.expert_bias, self.expert_bias_std = bool(expert_bias), float(expert_bias_std)
        self.bias_update_rate = float(bias_update_rate)
        self.dense_layers, self.dense_width = int(dense_layers), int(dense_width)
        if self.dense_layers and not self.dense_width:
            raise ValueError("a dense feed-forward needs its dense_width")
        self.tied_head = bool(tied_head)
        self.loop_steps, self.sandwich_norms, self.qk_norm = int(loop_steps), bool(sandwich_norms), bool(qk_norm)
        self.exit_gate, self.exit_entropy_weight = bool(exit_gate), float(exit_entropy_weight)
        if self.loop_steps < 1 or (self.exit_gate and self.loop_steps < 2):
            raise ValueError(f"an exit gate chooses among two passes or more, not {loop_steps}")
        if self.loop_steps > 1 and (self.dense_layers < self.n_layers or self.expert_bias):
            raise ValueError("a looped stack's layers are dense: a pass carries neither counters nor state")
        if self.exit_gate:  # what the exits' loss counts (nn/sequence.py); an expert layer's otherwise
            self.counter_names = seq.exit_counter_names(self.loop_steps)
        self.next_token_modules, self.next_token_loss_weight = int(next_token_modules), float(next_token_loss_weight)
        if self.next_token_modules not in (0, 1) or (self.next_token_modules and self.loop_steps > 1):
            raise ValueError("one module predicts the token after next, after a stack that is walked once")
        if self.next_token_modules:
            self.counter_names = moe.COUNTERS + seq.NEXT_COUNTERS
        if SPARSE in self.layer_types:
            if self.loop_steps > 1 or self.next_token_modules or self.sandwich_norms or self.index_top_k < 1:
                raise ValueError(f"{SPARSE} layers stand in a plain stack that is walked once and choose a key or more")
            self.counter_names = moe.COUNTERS + seq.SPARSE_COUNTERS
        self.rms_eps, self.init_std = float(rms_eps), float(init_std)
        self.embed_std = self.init_std if embed_std is None else float(embed_std)
        if self.tied_head and self.embed_std != self.init_std:
            raise ValueError("a tied head is the embedding: one scale for both")
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.attention_q_block, self.loss_chunk = int(attention_q_block), int(loss_chunk)
        self.mlp_chunk = int(mlp_chunk)

    def layer_kind(self, i: int) -> str:
        return self.layer_types[i]

    def divergent_state(self) -> bool:
        return False  # no buffers, or selection biases moved by counts summed over the data axis

    # ------------------------------------------------------------------ init --
    def init(self, key, x):
        e, std = self.hidden_size, self.init_std
        normal = lambda k, shape: std * jax.random.normal(k, shape, jnp.float32)
        # a norm's scale starts at 1: w = 0 where the scale is 1 + w
        norm = lambda n: (jnp.zeros if self.zero_centred_norms else jnp.ones)((n,), jnp.float32)

        def deltanet_mixer(k):
            ks = jax.random.split(k, 6)
            kd, vd = self.linear_k_heads * self.linear_k_dim, self.linear_v_heads * self.linear_v_dim
            hv = self.linear_v_heads
            # decay parameters as the Gated DeltaNet reference code draws them:
            # A uniform in (0, 16), dt log-uniform in [1e-3, 1e-1] through the
            # inverse of softplus
            dt = jnp.exp(jax.random.uniform(ks[4], (hv,), jnp.float32, math.log(1e-3), math.log(1e-1)))
            return {
                # columns: q (kd) | k (kd) | v (vd) | z (vd)
                "in_proj_qkvz": normal(ks[0], (e, 2 * kd + 2 * vd)),
                "in_proj_ba": normal(ks[1], (e, 2 * hv)),  # b (hv) | a (hv)
                "conv": jax.random.uniform(
                    ks[2], (self.conv_kernel, 2 * kd + vd), jnp.float32, -1.0, 1.0
                ) / math.sqrt(self.conv_kernel),
                "A_log": jnp.log(jax.random.uniform(ks[3], (hv,), jnp.float32, 1e-2, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm": jnp.ones((self.linear_v_dim,), jnp.float32),
                "out_proj": normal(ks[5], (vd, e)),
            }

        def attention_mixer(k, gated):
            ks = jax.random.split(k, 4)
            hq, hkv, d = self.n_heads, self.n_kv_heads, self.head_dim
            return {
                # gated: per head query (d) | gate (d)
                "q_proj": normal(ks[0], (e, hq * (2 if gated else 1) * d)),
                "k_proj": normal(ks[1], (e, hkv * d)),
                "v_proj": normal(ks[2], (e, hkv * d)),
                **({"q_norm": norm(d), "k_norm": norm(d)} if self.qk_norm else {}),
                "o_proj": normal(ks[3], (hq * d, e)),
            }

        def latent_mixer(k):
            ks = jax.random.split(k, 5)
            h, rq, rkv = self.n_heads, self.q_lora_rank, self.kv_lora_rank
            dn, dr, dv = self.qk_nope_dim, self.qk_rope_dim, self.v_head_dim
            return {
                "q_a_proj": normal(ks[0], (e, rq)), "q_a_norm": norm(rq),
                "q_b_proj": normal(ks[1], (rq, h * (dn + dr))),  # per head: without position (dn) | rotary (dr)
                "kv_a_proj": normal(ks[2], (e, rkv + dr)),  # the latent (rkv) | the one rotary key of all heads (dr)
                "kv_a_norm": norm(rkv),
                "kv_b_proj": normal(ks[3], (rkv, h * (dn + dv))),  # per head: key without position (dn) | value (dv)
                "o_proj": normal(ks[4], (h * dv, e)),
            }

        def sparse_mixer(k):
            k_attention, *ks = jax.random.split(k, 4)
            hi, di = self.index_heads, self.index_head_dim
            return {
                **attention_mixer(k_attention, False),
                # the indexer: its queries a head, its one key head and a weight a query head, all from the
                # layer's normed input; the language model's loss never reaches them
                "indexer": {
                    "q_proj": normal(ks[0], (e, hi * di)), "k_proj": normal(ks[1], (e, di)),
                    "k_norm": {"weight": jnp.ones((di,), jnp.float32), "bias": jnp.zeros((di,), jnp.float32)},
                    "w_proj": normal(ks[2], (e, hi)),
                },
            }

        def short_conv_mixer(k):
            ks = jax.random.split(k, 3)
            return {
                "in_proj": normal(ks[0], (e, 3 * e)),  # columns: b | c | u
                "conv": jax.random.uniform(
                    ks[1], (self.conv_kernel, e), jnp.float32, -1.0, 1.0
                ) / math.sqrt(self.conv_kernel),
                "out_proj": normal(ks[2], (e, e)),
            }

        def dense(k):
            ks = jax.random.split(k, 2)
            return {"gate_up": normal(ks[0], (e, 2 * self.dense_width)), "down": normal(ks[1], (self.dense_width, e))}

        def experts(k):
            ks = jax.random.split(k, 6)
            f, s, h = self.expert_width, self.shared_width, self.experts_held
            routed = {
                "router": normal(ks[0], (e, self.n_experts)),
                "experts": {"gate_up": normal(ks[1], (h, e, 2 * f)), "down": normal(ks[2], (h, f, e))},
            }
            if not s:  # no leaf, no shared expert (nn/moe.py)
                return routed
            return {
                **routed,
                "shared": {"gate_up": normal(ks[3], (e, 2 * s)), "down": normal(ks[4], (s, e))},
                **({"shared_gate": normal(ks[5], (e, 1))} if self.shared_gate else {}),  # no leaf, no gate
            }

        def mixer_of(kind, k):
            if kind == DELTANET:
                return deltanet_mixer(k)
            if kind == SHORT_CONV:
                return short_conv_mixer(k)
            if kind == LATENT:
                return latent_mixer(k)
            if kind == SPARSE:
                return sparse_mixer(k)
            return attention_mixer(k, kind == ATTENTION)

        def drawn_bias(k):
            return {"expert_bias": self.expert_bias_std * jax.random.normal(
                jax.random.fold_in(k, 1), (self.n_experts,), jnp.float32
            )}

        k_embed, k_head, k_layers = jax.random.split(key, 3)
        layers, biases = [], []
        for i in range(self.n_layers):
            k_mixer, k_moe = jax.random.split(jax.random.fold_in(k_layers, i))
            sparse = i >= self.dense_layers
            layers.append({
                "input_norm": norm(e), "mixer": mixer_of(self.layer_kind(i), k_mixer), "post_norm": norm(e),
                **({"mixer_out_norm": norm(e), "ff_out_norm": norm(e)} if self.sandwich_norms else {}),
                **({"moe": experts(k_moe)} if sparse else {"mlp": dense(k_moe)}),
            })
            if self.expert_bias:
                biases.append(drawn_bias(k_moe) if sparse else ())
        params = {
            "embed": {"weight": self.embed_std * jax.random.normal(k_embed, (self.vocab_size, e), jnp.float32)},
            "layers": tuple(layers),
            "final_norm": norm(e),
        }
        if not self.tied_head:
            params["head"] = {"weight": normal(k_head, (e, self.vocab_size))}
        if self.exit_gate:  # from 0: a fresh gate halves what is left at every exit
            params["exit_gate"] = {"weight": jnp.zeros((e, 1), jnp.float32), "bias": jnp.zeros((1,), jnp.float32)}
        if self.next_token_modules:
            k_proj, k_mixer, k_moe = jax.random.split(jax.random.fold_in(k_layers, self.n_layers), 3)
            params["mtp"] = {
                "hidden_norm": norm(e), "embed_norm": norm(e),
                "proj": normal(k_proj, (2 * e, e)),  # rows: the state's (e) | the next token's embedding's (e)
                "layer": {"input_norm": norm(e), "mixer": mixer_of(self.layer_types[-1], k_mixer),
                          "post_norm": norm(e), "moe": experts(k_moe)},
                "head_norm": norm(e),
            }
            if self.expert_bias:  # the module's router has a bias of its own, after the layers'
                biases.append(drawn_bias(k_moe))
        return params, tuple(biases)  # a selection bias a sparse layer (the module's last), or nothing

    # --------------------------------------------------------------- mixers --
    def _norm(self, x, w):
        return seq.rms_norm(x, w, self.rms_eps, zero_centred=self.zero_centred_norms)

    def _deltanet(self, p, x):
        b, t, _ = x.shape
        cd, f32 = self.compute_dtype, jnp.float32
        hk, hv, dk, dv = self.linear_k_heads, self.linear_v_heads, self.linear_k_dim, self.linear_v_dim
        with _prof.scope("in_proj"):
            qkvz = seq.matmul(x, p["in_proj_qkvz"], cd)
            ba = seq.matmul(x, p["in_proj_ba"], cd, f32)
            qkv, z = qkvz[..., : 2 * hk * dk + hv * dv], qkvz[..., 2 * hk * dk + hv * dv:]
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
        with _prof.scope("conv"):
            # causal convolution, SiLU and the l2 norms of queries and keys: one function with its own
            # backward rule, a fused kernel pair where deltanet.conv_lowering takes the shapes
            q, k, v = deltanet.short_conv(
                qkv, p["conv"], key_width=hk * dk, head_dim=dk, q_scale=dk ** -0.5
            )
            q, k, v = q.reshape(b, t, hk, dk), k.reshape(b, t, hk, dk), v.reshape(b, t, hv, dv)
        with _prof.scope("scan"):  # each key head serves hv / hk value heads: the scan's own business
            o = deltanet.chunk_gated_delta_rule(q, k, v, g, beta, chunk=self.chunk, compute_dtype=cd)
        with _prof.scope("out_proj"):
            o = seq.rms_norm(o, p["norm"], self.rms_eps, gate=z.reshape(b, t, hv, dv))
            return seq.matmul(o.reshape(b, t, hv * dv), p["out_proj"], cd)

    def _attention(self, p, x, kind=ATTENTION):
        """The three softmax-attention types: what is theirs alone is the
        gate (``GatedAttention``), the window (``SlidingAttention``) and the
        scaled rotary table (``FullAttention``)."""
        b, t, _ = x.shape
        cd = self.compute_dtype
        hq, hkv, d = self.n_heads, self.n_kv_heads, self.head_dim
        gated = kind == ATTENTION
        with _prof.scope("qkv"):
            q = seq.matmul(x, p["q_proj"], cd)
            if gated:
                qg = q.reshape(b, t, hq, 2 * d)
                q, gate = qg[..., :d], qg[..., d:]
            else:
                q = q.reshape(b, t, hq, d)
            k = seq.matmul(x, p["k_proj"], cd).reshape(b, t, hkv, d)
            v = seq.matmul(x, p["v_proj"], cd).reshape(b, t, hkv, d)
            positions = jnp.arange(t)
            rope = functools.partial(
                seq.rotary, positions=positions, rotary_dim=self.rotary_dim, theta=self.rope_theta,
                yarn=self.yarn if kind == FULL else None,
            )
            # the tree says whether queries and keys have a norm a head
            head_norm = (lambda a, w: self._norm(a, p[w])) if "q_norm" in p else (lambda a, w: a)
            q, k = rope(head_norm(q, "q_norm")), rope(head_norm(k, "k_norm"))
        with _prof.scope("attention"):
            o = seq.causal_attention(
                q, k, v, scale=d ** -0.5, compute_dtype=cd, q_block=self.attention_q_block,
                window=self.sliding_window if kind == SLIDING else None,
            )  # q_block: the blockwise lowering's; the fused kernel has its own
        with _prof.scope("o_proj"):
            if gated:
                o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
            return seq.matmul(o.reshape(b, t, hq * d), p["o_proj"], cd)

    def _latent_attention(self, p, x):
        """Attention whose queries, keys and values come through low-rank
        latents (arXiv:2405.04434, section 2.1), in the decompressed form
        training runs: a norm inside each low-rank pair, a head's queries and
        keys a part without position beside a rotary part, and ONE rotary key
        a token that every head shares (it is projected from the input, not
        from the latent). Scores are ``qk_nope_dim + qk_rope_dim`` wide,
        values ``v_head_dim``: the same width, so :func:`seq.causal_attention`
        takes the heads as it takes any others, ungrouped."""
        b, t, _ = x.shape
        cd = self.compute_dtype
        h, rkv, dn, dr, dv = self.n_heads, self.kv_lora_rank, self.qk_nope_dim, self.qk_rope_dim, self.v_head_dim
        rope = functools.partial(seq.rotary, positions=jnp.arange(t), rotary_dim=dr, theta=self.rope_theta)
        with _prof.scope("q_latent"):
            q = seq.matmul(self._norm(seq.matmul(x, p["q_a_proj"], cd), p["q_a_norm"]), p["q_b_proj"], cd)
            q = q.reshape(b, t, h, dn + dr)
            q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], axis=-1)
        with _prof.scope("kv_latent"):
            latent = seq.matmul(x, p["kv_a_proj"], cd)
            kv = seq.matmul(self._norm(latent[..., :rkv], p["kv_a_norm"]), p["kv_b_proj"], cd).reshape(b, t, h, dn + dv)
            k_rope = rope(latent[..., None, rkv:])  # (B, T, 1, dr): the same rotated key for every head
            k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (b, t, h, dr))], axis=-1)
            v = kv[..., dn:]
        with _prof.scope("attention"):
            o = seq.causal_attention(
                q, k, v, scale=(dn + dr) ** -0.5, compute_dtype=cd, q_block=self.attention_q_block
            )
        with _prof.scope("o_proj"):
            return seq.matmul(o.reshape(b, t, h * dv), p["o_proj"], cd)

    def _sparse_mix(self, p, x, remat: bool):
        """``x + Attn(RMSNorm(x))`` for one sequence ``(T, E)`` of a
        ``SparseAttention`` layer, the indexer's objective summed over the
        rows and the pairs its selections hold: grouped-query attention with
        the per-head norm and rotary on the whole head, in which a query
        attends only the ``index_top_k`` keys its indexer scored highest
        (``nn/sequence.py``: ``sparse_attention_rows``). The indexer reads the
        layer's normed input behind a stop-gradient: queries a head under
        rotary, ONE key head under a LayerNorm and rotary, a weight a head
        scaled by ``index_heads^-1/2 index_head_dim^-1/2``.

        Keys, values and the indexer's keys are made once a sequence and kept
        in float32 (their gradients add up over the groups); the queries go a
        group of rows at a time with everything that is ``rows x keys`` (index
        scores, the selection, attention, the objective), the groups of a
        stretch in one rolled loop. With ``remat`` the keys and each group are
        recomputed in the backward pass: the layer is its own checkpoint, so
        that a group is computed twice a step and not three times."""
        m, ix, cd, f32 = p["mixer"], p["mixer"]["indexer"], self.compute_dtype, jnp.float32
        t = x.shape[0]
        hq, hkv, d, hi, di = self.n_heads, self.n_kv_heads, self.head_dim, self.index_heads, self.index_head_dim
        lowering = seq.sparse_attention_lowering(jax.default_backend(), d, t, per_replica=seq.traced_per_replica())
        group = seq.SPARSE_GROUP if lowering == "fused" else min(self.attention_q_block, t)
        rope = lambda a, positions: seq.rotary(a, positions, rotary_dim=a.shape[-1], theta=self.rope_theta)
        # the tree says whether queries and keys have a norm a head
        head_norm = (lambda a, w: self._norm(a, m[w])) if "q_norm" in m else (lambda a, w: a)

        def keys(m, ix, norm, x):
            h, positions = self._norm(x, norm)[None], jnp.arange(t)
            with _prof.scope("qkv"):
                k = rope(head_norm(seq.matmul(h, m["k_proj"], cd, f32).reshape(1, t, hkv, d), "k_norm"), positions)
                v = seq.matmul(h, m["v_proj"], cd, f32).reshape(t, hkv, d)
            with _prof.scope("index_proj"):
                ki = seq.matmul(seq.indexer_input(h), ix["k_proj"], cd, f32)
                ki = seq.layer_norm(ki, ix["k_norm"]["weight"], ix["k_norm"]["bias"], self.rms_eps)
                ki = rope(ki[:, :, None, :], positions)
            return k[0], v, ki[0, :, 0]

        def rows(m, ix, norm, x_rows, start, k, v, ki):
            g = x_rows.shape[0]
            h, positions = self._norm(x_rows, norm)[None], start + jnp.arange(g)
            with _prof.scope("qkv"):
                q = rope(head_norm(seq.matmul(h, m["q_proj"], cd).reshape(1, g, hq, d), "q_norm"), positions)
            with _prof.scope("index_proj"):
                behind = seq.indexer_input(h)
                qi = rope(seq.matmul(behind, ix["q_proj"], cd, f32).reshape(1, g, hi, di), positions)
                wi = seq.matmul(behind, ix["w_proj"], cd, f32) * (hi ** -0.5 * di ** -0.5)
            out, kl, pairs = seq.sparse_attention_rows(
                q[0], qi[0], wi[0], k, v, ki, start, scale=d ** -0.5, top_k=self.index_top_k,
                compute_dtype=cd, lowering=lowering,
            )
            with _prof.scope("o_proj"):
                return x_rows + seq.matmul(out.reshape(g, hq * d), m["o_proj"], cd), kl, pairs

        if remat:
            keys, rows = jax.checkpoint(keys), jax.checkpoint(rows)
        k, v, ki = keys(m, ix, p["input_norm"], x)
        out, kl, pairs = [], jnp.zeros((), f32), jnp.zeros((), f32)
        for first, n, end in seq.sparse_row_groups(t, group):
            size = min(group, n)
            y, kl_groups, pairs_groups = jax.lax.map(
                lambda args: rows(m, ix, p["input_norm"], *args, k[:end], v[:end], ki[:end]),
                (x[first:first + n].reshape(n // size, size, -1), first + size * jnp.arange(n // size)),
            )
            out.append(y.reshape(n, -1))
            kl, pairs = kl + jnp.sum(kl_groups), pairs + jnp.sum(pairs_groups)
        return jnp.concatenate(out), kl, pairs

    def _short_conv(self, p, x):
        cd = self.compute_dtype
        with _prof.scope("in_proj"):
            bcu = seq.matmul(x, p["in_proj"], cd)
        with _prof.scope("conv"):
            z = seq.gated_short_conv(bcu, p["conv"])
        with _prof.scope("out_proj"):
            return seq.matmul(z, p["out_proj"], cd)

    def _mix(self, kind, p, x):
        """``x + Mixer(RMSNorm(x))`` for one sequence ``(T, E)``; where the
        layer's tree has ``mixer_out_norm``, that norm on the mixer's result
        before the add."""
        if kind == DELTANET:
            mixer = self._deltanet
        elif kind == SHORT_CONV:
            mixer = self._short_conv
        elif kind == LATENT:
            mixer = self._latent_attention
        else:
            mixer = functools.partial(self._attention, kind=kind)
        y = mixer(p["mixer"], self._norm(x[None], p["input_norm"]))[0]
        if "mixer_out_norm" in p:
            y = self._norm(y, p["mixer_out_norm"])
        return x + y

    def _experts(self, p, bias, h):
        with _prof.scope("moe"):
            y, aux, counters, router_counts = moe.expert_share_moe(
                p["moe"], self._norm(h, p["post_norm"]).reshape(-1, self.hidden_size),
                top_k=self.top_k, first_expert=self.first_expert, compute_dtype=self.compute_dtype, bias=bias,
                scale=self.routed_scale,
            )
        y = y.reshape(h.shape)
        if "ff_out_norm" in p:
            y = self._norm(y, p["ff_out_norm"])
        return h + y, aux, counters, router_counts

    def _dense(self, p, h, remat: bool):
        """``h + SwiGLU(RMSNorm(h))``, ``mlp_chunk`` tokens at a time (with
        ``remat`` each chunk recomputed in the backward pass): the products'
        float32 results are ``2 dense_width`` wide a token. Where the layer's
        tree has ``ff_out_norm``, that norm on the SwiGLU's result before the
        add."""
        def chunk(rows):
            with _prof.scope("mlp"):
                x = self._norm(rows, p["post_norm"])
                y = seq.swiglu(x, p["mlp"]["gate_up"], p["mlp"]["down"], self.compute_dtype)
                if "ff_out_norm" in p:
                    y = self._norm(y, p["ff_out_norm"])
                return rows + y

        if remat:
            chunk = jax.checkpoint(chunk)
        rows = h.reshape(-1, self.hidden_size)
        n, size = rows.shape[0], min(self.mlp_chunk, rows.shape[0])
        whole, out = n // size, []
        if whole:
            out.append(jax.lax.map(chunk, rows[:whole * size].reshape(whole, size, -1)).reshape(whole * size, -1))
        if whole * size < n:
            out.append(chunk(rows[whole * size:]))
        return jnp.concatenate(out).reshape(h.shape)

    def _layer(self, kind, p, bias, x, remat: bool):
        """One layer: ``(y, aux_loss, counters, router_counts)``, the last
        three ``None`` for a dense feed-forward. The mixer takes the batch's
        sequences one at a time and the feed-forward all their tokens at once
        (a dense one in chunks of them); with ``remat`` each of the two is
        recomputed in the backward pass, so a step keeps the residual stream
        at both and one sequence's mixer or one expert layer's activations."""
        mix = functools.partial(self._mix, kind)
        if remat:
            mix = jax.checkpoint(mix)
        return self._feed_forward(p, bias, jax.lax.map(lambda sequence: mix(p, sequence), x), remat)

    def _feed_forward(self, p, bias, h, remat: bool):
        """The second half of a layer, whichever its tree holds."""
        if "moe" not in p:  # the tree says which feed-forward a layer has
            return self._dense(p, h, remat), None, None, None
        return (jax.checkpoint(self._experts) if remat else self._experts)(p, bias, h)

    def _sparse_layer(self, p, bias, x, remat: bool):
        """A ``SparseAttention`` layer: :meth:`_layer`'s four and a fifth, the
        layer's own objective as a mean over the rows (its gradient reaches
        the indexer alone); its counters stand beside an expert layer's. The
        mixer is its own checkpoint, a group of queries each."""
        h, kl, pairs = jax.lax.map(lambda sequence: self._sparse_mix(p, sequence, remat), x)
        rows = jnp.asarray(kl.size * x.shape[1], jnp.float32)
        counters = dict(zip(seq.SPARSE_COUNTERS, jax.lax.stop_gradient((jnp.sum(kl), rows, jnp.sum(pairs)))))
        y, aux, expert_counters, router_counts = self._feed_forward(p, bias, h, remat)
        return y, aux, {**counters, **(expert_counters or {})}, router_counts, jnp.sum(kl) / rows

    # -------------------------------------------------------------- forward --
    def _scoped_layer(self, i: int, kind: str, p, layer_state, h, ctx: Context):
        """Layer ``i`` under its scope ``<i>_<kind>``: ``(y, aux_loss,
        counters, the layer's state after the step, index_loss)``; in
        training a sparse layer's selection bias moves by the step's counts."""
        bias = layer_state["expert_bias"] if layer_state else None
        with _prof.scope(f"{i}_{kind}"):
            if kind == SPARSE:
                h, aux, counters, router_counts, index_loss = self._sparse_layer(p, bias, h, ctx.train)
            else:
                (h, aux, counters, router_counts), index_loss = self._layer(kind, p, bias, h, ctx.train), None
            if bias is not None and ctx.train:
                with _prof.scope("moe"), _prof.scope("router"):
                    layer_state = {"expert_bias": moe.balanced_bias(
                        bias, router_counts, self.bias_update_rate, ctx.axis_name
                    )}
        return h, aux, counters, layer_state, index_loss

    def _layers(self, params, state, h, ctx: Context):
        """One walk over the layers: ``(h, aux_loss, counters, new_state,
        index_loss)``: the routers' load-balancing losses summed, and the
        indexers' objectives summed (``None`` where no layer has one); each
        enters the gradient at a weight of its own (:meth:`apply`)."""
        aux_total, index_total = jnp.zeros((), jnp.float32), None
        layer_counters = moe.COUNTERS + seq.SPARSE_COUNTERS
        totals = {name: jnp.zeros((), jnp.float32) for name in layer_counters if name in self.counter_names}
        new_state = list(state)  # a selection bias a sparse layer, where the model has them
        for i, p in enumerate(params["layers"]):
            h, aux, counters, layer_state, index_loss = self._scoped_layer(
                i, self.layer_kind(i), p, state[i] if state else (), h, ctx
            )
            if state:
                new_state[i] = layer_state
            if aux is not None:
                aux_total = aux_total + aux
            if index_loss is not None:
                index_total = index_loss if index_total is None else index_total + index_loss
            if counters is not None:
                totals = {name: total + counters.get(name, 0.0) for name, total in totals.items()}
        return h, aux_total, totals, tuple(new_state), index_total

    def _next_token_module(self, p, layer_state, embed, tokens, h, ctx: Context):
        """The states the second head reads (arXiv:2412.19437, section 2.2, at
        depth 1): position ``i``'s trunk state ``h_i`` (before the final norm)
        and the embedding of token ``i + 1``, each normed, joined and
        projected back to the model's width, through one more layer of the
        last layer's type with a sparse feed-forward of its own, then the
        module's own norm; the head and the embedding are the model's.
        ``(states, aux_loss, counters, the module's state after the step)``.
        A sequence's last position has no successor: it is fed the first
        token's embedding, and the loss gives it weight 0
        (:class:`~tpuddp.nn.sequence.DeferredLogits`); causal, it reaches no
        other position's state, only the router's counts, as one token."""
        with _prof.scope("mtp"):
            with _prof.scope("proj"):
                after = seq.round_to(jnp.take(embed, jnp.roll(tokens, -1, axis=-1), axis=0), self.compute_dtype)
                joined = jnp.concatenate(
                    [self._norm(h, p["hidden_norm"]), self._norm(after, p["embed_norm"])], axis=-1
                )
                h = seq.matmul(joined, p["proj"], self.compute_dtype)
            h, aux, counters, layer_state, _ = self._scoped_layer(
                self.n_layers, self.layer_types[-1], p["layer"], layer_state, h, ctx
            )
            return self._norm(h, p["head_norm"]), aux, counters, layer_state

    def _pass(self, params, state, h, ctx: Context):
        """One pass of a looped stack: ``(what the next pass takes in, this
        pass's exit)``, one and the same normed state. In training a pass
        keeps its input alone and is walked again in the backward pass, where
        each layer then keeps the residual stream twice as in one walk: kept
        for every pass, those rows are ``2 * loop_steps * n_layers`` copies of
        the stream and do not fit beside the optimizer's state at the
        published widths (PERF.md, PR 42)."""
        def walk(params, h):
            h = self._norm(self._layers(params, state, h, ctx)[0], params["final_norm"])
            return h, h

        return (jax.checkpoint(walk) if ctx.train else walk)(params, h)

    def apply(self, params, state, x, ctx: Context):
        tokens = jnp.asarray(x).astype(jnp.int32)
        # the residual stream is kept in the products' input type (an 8-bit
        # type only rounds the products' inputs: round_to)
        h = seq.round_to(jnp.take(params["embed"]["weight"], tokens, axis=0), self.compute_dtype)
        exits = next_hidden = None
        if self.loop_steps == 1:
            h, aux_total, totals, new_state, index_total = self._layers(params, state, h, ctx)
            if "mtp" in params and ctx.train:  # the tree says whether a second head predicts the token after next
                module_state = state[self.n_layers] if state else ()
                next_hidden, aux, counters, module_state = self._next_token_module(
                    params["mtp"], module_state, params["embed"]["weight"], tokens, h, ctx
                )
                aux_total = aux_total + aux
                totals = {name: totals[name] + counters[name] for name in totals}
                if state:
                    new_state = (*new_state[:self.n_layers], module_state)
            h = self._norm(h, params["final_norm"])
        else:
            # one rolled loop over the same leaves: the program holds the
            # stack once, whatever loop_steps is
            with _prof.scope("passes"):
                h, exits = jax.lax.scan(
                    lambda h, _: self._pass(params, state, h, ctx), h, None, length=self.loop_steps
                )
            # dense layers, no state (the constructor holds that): nothing to add up over the passes
            aux_total, index_total, new_state = None, None, tuple(state)
            totals = {name: jnp.zeros((), jnp.float32) for name in self.counter_names}
        head = params["head"]["weight"] if "head" in params else params["embed"]["weight"].T
        # what enters the gradient beside the language model's loss, each at its own weight
        extra = None if aux_total is None else self.aux_loss_weight * aux_total
        if index_total is not None:
            extra = extra + self.indexer_loss_weight * index_total
        if exits is not None and "exit_gate" in params and ctx.train:  # the tree says whether the passes are exits
            return seq.DeferredExits(
                exits, head, params["exit_gate"], entropy_weight=self.exit_entropy_weight,
                compute_dtype=self.compute_dtype, chunk=self.loss_chunk,
            ), new_state
        out = seq.DeferredLogits(
            h, head, extra, totals, next_hidden,
            compute_dtype=self.compute_dtype, chunk=self.loss_chunk, next_weight=self.next_token_loss_weight,
        )
        return (out if ctx.train else out.logits()), new_state


QWEN3_NEXT_EP16 = dict(  # Qwen3-Next-80B-A3B's widths; depth, experts held and vocabulary cut
    hidden_size=2048, n_layers=4, full_attention_interval=4,
    n_heads=16, n_kv_heads=2, head_dim=256, partial_rotary_factor=0.25, rope_theta=1e7,
    linear_k_heads=16, linear_v_heads=32, linear_k_dim=128, linear_v_dim=128, conv_kernel=4,
    n_experts=512, experts_held=32, first_expert=0, top_k=10, expert_width=512, shared_width=512,
)
MELLUM2_EP4 = dict(  # Mellum2-12B-A2.5B's widths; depth, experts held and vocabulary cut
    hidden_size=2304, n_layers=4, layer_types=(SLIDING, SLIDING, SLIDING, FULL), zero_centred_norms=False,
    n_heads=32, n_kv_heads=4, head_dim=128, partial_rotary_factor=1.0, rope_theta=5e5, sliding_window=1024,
    yarn=dict(factor=16, original_max_position_embeddings=8192, beta_fast=32, beta_slow=1,
              attention_factor=1.2772588722239782),
    n_experts=64, experts_held=16, first_expert=0, top_k=8, expert_width=896, shared_width=0,
)
LFM2_EP4 = dict(  # LFM2-8B-A1B's widths; depth, dense layers, experts held and vocabulary cut
    hidden_size=2048, n_layers=5, layer_types=(SHORT_CONV, FULL, SHORT_CONV, SHORT_CONV, SHORT_CONV),
    zero_centred_norms=False, n_heads=32, n_kv_heads=8, head_dim=64, partial_rotary_factor=1.0, rope_theta=1e6,
    conv_kernel=3, dense_layers=1, dense_width=7168, tied_head=True, rms_eps=1e-5,
    n_experts=32, experts_held=8, first_expert=0, top_k=4, expert_width=1792, shared_width=0,
    expert_bias=True, bias_update_rate=1e-3, aux_loss_weight=0.0,
)
OURO_2_6B_L6 = dict(  # Ouro-2.6B's widths, heads and passes; depth cut. Every layer whole on the chip
    hidden_size=2048, n_layers=6, layer_types=(FULL,) * 6, zero_centred_norms=False,
    n_heads=16, n_kv_heads=16, head_dim=128, partial_rotary_factor=1.0, rope_theta=1e6, qk_norm=False,
    dense_layers=6, dense_width=5632, sandwich_norms=True, loop_steps=4, exit_gate=True, exit_entropy_weight=0.05,
)
GLM_4_7_FLASH_EP8 = dict(  # GLM-4.7-Flash's widths, heads and ranks; depth, experts held and vocabulary cut
    hidden_size=2048, n_layers=5, layer_types=(LATENT,) * 5, zero_centred_norms=False,
    n_heads=20, n_kv_heads=20, q_lora_rank=768, kv_lora_rank=512, qk_nope_dim=192, qk_rope_dim=64, v_head_dim=256,
    rope_theta=1e6, dense_layers=1, dense_width=10240, rms_eps=1e-5,
    n_experts=64, experts_held=8, first_expert=0, top_k=4, expert_width=1536, shared_width=1536, shared_gate=False,
    routed_scale=1.8, expert_bias=True, bias_update_rate=1e-3, aux_loss_weight=0.0,
    next_token_modules=1, next_token_loss_weight=0.3, embed_std=1.0,
)
KEYE_VL_2_0_EP8 = dict(  # Keye-VL-2.0-30B-A3B's language model: widths, heads and indexer; depth, experts held and vocabulary cut
    hidden_size=2048, n_layers=5, layer_types=(SPARSE,) * 5, zero_centred_norms=False,
    n_heads=32, n_kv_heads=4, head_dim=128, partial_rotary_factor=1.0, rope_theta=1e7,
    index_heads=16, index_head_dim=64, index_top_k=2048, indexer_loss_weight=1.0,
    n_experts=128, experts_held=16, first_expert=0, top_k=8, expert_width=768, shared_width=0,
    aux_loss_weight=16.0, embed_std=1.0,
)
QWEN3_NEXT_TINY = dict(
    hidden_size=64, n_layers=4, full_attention_interval=4,
    n_heads=4, n_kv_heads=2, head_dim=16, partial_rotary_factor=0.25, rope_theta=1e7,
    linear_k_heads=2, linear_v_heads=4, linear_k_dim=16, linear_v_dim=16, conv_kernel=4, chunk=16,
    n_experts=8, experts_held=2, first_expert=0, top_k=2, expert_width=32, shared_width=32,
    attention_q_block=32, loss_chunk=64,
)
MELLUM2_TINY = dict(  # a window that is shorter than the tests' sequences and no multiple of the query block
    hidden_size=64, n_layers=4, layer_types=(SLIDING, SLIDING, SLIDING, FULL), zero_centred_norms=False,
    n_heads=4, n_kv_heads=2, head_dim=16, partial_rotary_factor=1.0, rope_theta=100.0, sliding_window=20,
    yarn=dict(factor=4, original_max_position_embeddings=32, beta_fast=2, beta_slow=0.25,
              attention_factor=1.1386294361119891),
    n_experts=8, experts_held=2, first_expert=0, top_k=2, expert_width=32, shared_width=0,
    attention_q_block=16, loss_chunk=64,
)
LFM2_TINY = dict(  # the dense feed-forward in chunks of 32 tokens and what is left of the tests' sequences
    hidden_size=64, n_layers=5, layer_types=(SHORT_CONV, FULL, SHORT_CONV, SHORT_CONV, SHORT_CONV),
    zero_centred_norms=False, n_heads=4, n_kv_heads=2, head_dim=16, partial_rotary_factor=1.0, rope_theta=1e4,
    conv_kernel=3, dense_layers=1, dense_width=96, tied_head=True, rms_eps=1e-5,
    n_experts=8, experts_held=2, first_expert=0, top_k=2, expert_width=32, shared_width=0,
    expert_bias=True, bias_update_rate=1e-3, aux_loss_weight=0.0,
    attention_q_block=16, loss_chunk=64, mlp_chunk=32,
)
OURO_TINY = dict(  # three layers four times over; the dense feed-forward and the exits' loss in chunks with a rest
    hidden_size=64, n_layers=3, layer_types=(FULL,) * 3, zero_centred_norms=False,
    n_heads=4, n_kv_heads=4, head_dim=16, partial_rotary_factor=1.0, rope_theta=1e4, qk_norm=False,
    dense_layers=3, dense_width=96, sandwich_norms=True, loop_steps=4, exit_gate=True, exit_entropy_weight=0.05,
    attention_q_block=16, loss_chunk=64, mlp_chunk=32,
)
GLM_4_7_FLASH_TINY = dict(  # a dense leading layer and two sparse ones, heads of 12 + 4, the second head's module
    hidden_size=64, n_layers=3, layer_types=(LATENT,) * 3, zero_centred_norms=False,
    n_heads=4, n_kv_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=12, qk_rope_dim=4, v_head_dim=16,
    rope_theta=1e4, dense_layers=1, dense_width=96, rms_eps=1e-5,
    n_experts=8, experts_held=2, first_expert=0, top_k=2, expert_width=32, shared_width=32, shared_gate=False,
    routed_scale=1.8, expert_bias=True, bias_update_rate=1e-3, aux_loss_weight=0.0,
    next_token_modules=1, next_token_loss_weight=0.3, embed_std=1.0,
    attention_q_block=16, loss_chunk=64, mlp_chunk=32,
)
KEYE_VL_2_0_TINY = dict(  # two sparse-attention layers whose queries choose 8 keys, an indexer of 2 heads of 16
    hidden_size=64, n_layers=2, layer_types=(SPARSE,) * 2, zero_centred_norms=False,
    n_heads=4, n_kv_heads=2, head_dim=16, partial_rotary_factor=1.0, rope_theta=1e4,
    index_heads=2, index_head_dim=16, index_top_k=8, indexer_loss_weight=1.0,
    n_experts=8, experts_held=2, first_expert=0, top_k=2, expert_width=32, shared_width=0,
    aux_loss_weight=16.0, embed_std=1.0,
    attention_q_block=16, loss_chunk=64,
)
