"""Decoder/encoder transformer family: GPT, Llama-2, BERT-class.

TPU-first design choices:

- **Stacked layers + scan**: all layer params carry a leading [n_layers]
  dim and the forward pass is one ``lax.scan`` — compile time stays flat in
  depth and XLA pipelines the layer loop cleanly.
- **Logical axes on every param** (transformer_logical_axes) so
  parallel.sharding.ShardingRules decides DP/FSDP/TP placement; the model
  never mentions mesh axes.
- **bf16 activations, f32 params**: matmuls hit the MXU in bfloat16; the
  loss/softmax runs in f32.
- **Ring attention** over a cp axis is a drop-in (attn_impl="ring") for
  long-context jobs; default is dense attention, which XLA fuses well.
- **Remat**: optional jax.checkpoint per layer to trade FLOPs for HBM.

Architecture follows the Llama-2 recipe (RMSNorm, rotary embeddings, GQA,
SwiGLU) with ``causal=False`` turning the same core into a BERT-class
bidirectional encoder (MLM head = the same tied vocab projection).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, fields, replace
from functools import cache, partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from tf_operator_tpu.parallel.mesh import AXIS_CONTEXT, AXIS_EXPERT, AXIS_PIPELINE


# The KINDS a layer's mixer is one of, by name. Two are ``layer_pattern``
# entries as they stand — LINEAR (Gated DeltaNet) and MAMBA (Mamba-1's
# selective scan): the RECURRENT kinds, whose sequence state has one size
# however long the sequence — and a layer that attends (a (window, rotary)
# entry) is of kind ATTN. A mixer's leaves are stacked by kind.
LINEAR = "linear"
MAMBA = "mamba"
ATTN = "attn"
RECURRENT = (LINEAR, MAMBA)


def kind_of(entry) -> str:
    """A ``layer_pattern`` entry's kind."""
    return entry if entry in RECURRENT else ATTN


@dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    d_ff: int = 11008
    max_seq: int = 4096
    causal: bool = True
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # True/"full": save only layer inputs, recompute everything (min HBM,
    # +2ND FLOPs). "dots": selective checkpointing — save matmul outputs,
    # recompute just the elementwise chain (near-6ND at moderate HBM).
    # False/"none": no remat (max HBM). A _REMAT_SAVE_SETS alias
    # ("save_mid", ...) or "save:name1,name2": save the named activations
    # only (KNOWN_SAVE_NAMES; the *_mid tiers keep flash_o/flash_lse, so
    # the flash forward is not replayed).
    remat: Any = True
    # "dense" | "flash" (Pallas kernel) | "ring" (cp ppermute ring) |
    # "ulysses" (cp all-to-all head/seq re-shard; needs heads % cp == 0)
    attn_impl: str = "dense"
    # Blockwise fused loss (ops/fused_cross_entropy): logits never hit HBM
    # as a [b,t,vocab] f32 array. Same math as the unfused path.
    fused_xent: bool = True
    # Mixture-of-experts MLP (parallel.moe): 0 = dense. moe_top_k=1 is
    # Switch-style; 2 is Mixtral-style (renormalized gate weights).
    # Experts shard over the ep mesh axis (all-to-all dispatch); without an
    # ep axis all experts run on every device (the routing math is
    # identical, so one config tests on CPU and scales on a pod).
    n_experts: int = 0
    moe_top_k: int = 1
    capacity_factor: float = 2.0
    # Expert dispatch: "sort" (capacity queues + scatter/gather, the ep
    # all_to_all layout), "einsum" (one-hot oracle), or "gmm" (r5/r6 —
    # the Pallas grouped-matmul kernel: block-granular padding only, no
    # drops; ops/grouped_matmul.py). r6: gmm runs under ep sharding too
    # (count-exchange + block-quantum all_to_all buffers,
    # parallel.moe._moe_local_gmm) including ep-inside-pipeline.
    moe_dispatch: str = "sort"
    # Router auxiliary losses — without them top-k routing collapses onto a
    # few experts under real training. moe_aux_weight scales the Switch
    # load-balance loss  E * Σ_e f_e·P_e  (f_e = fraction of token-choices
    # assigned to expert e — non-differentiable, acts as the coefficient;
    # P_e = mean router probability — carries the gradient; uniform routing
    # gives exactly 1.0). moe_zloss_weight scales the ST-MoE router z-loss
    # mean(logsumexp(router_logits)²), which keeps router logits from
    # drifting to magnitudes where softmax saturates and bf16 rounds.
    # Both default ON for MoE configs (0.0 disables — the ablation knob).
    moe_aux_weight: float = 0.01
    moe_zloss_weight: float = 1e-3
    # Pipeline parallelism (parallel.pipeline): with a pp axis in the mesh
    # and pp_microbatches > 0, the layer stack is stage-partitioned into
    # mesh.shape["pp"] groups of n_layers/pp contiguous layers and run as a
    # fill-drain pipeline (activations ppermute stage-to-stage);
    # embed/norm/head stay replicated. Composes with dp (each dp group
    # pipelines its own batch slice) and, r3, with tp (stage weights shard
    # over the tp axis; _layer psums its row-parallel matmuls). 0 = no
    # pipeline. pp_schedule: "1f1b" (explicit backward, stage-input-only
    # residuals — the memory-disciplined default) | "gpipe" (autodiff).
    # pp_chunks (r3): virtual stages per device — the INTERLEAVED 1F1B
    # schedule. n_layers splits into pp*pp_chunks chunks (chunk j on
    # device j mod pp, model order); bubble shrinks from
    # (pp-1)/(M+pp-1) to (pp-1)/(M*v+pp-1). Requires pp_schedule="1f1b"
    # and pp_microbatches % pp == 0.
    pp_microbatches: int = 0
    pp_schedule: str = "1f1b"
    pp_chunks: int = 1
    # Head width when it is its own number (0: d_model // n_heads). With
    # d_head set, wq/wo are [d_model, n_heads·d_head] — not square.
    d_head: int = 0
    # The layer pattern: one (window, rotary) entry a position of the
    # PERIOD, repeated n_layers / len(pattern) times. window 0 = every
    # earlier key (global), W > 0 = the last W keys; rotary False = no
    # positional encoding at all in that layer (NoPE). () is one entry
    # (0, True): today's stack. The stack scans over periods with the
    # period's layers unrolled in the body, so both are static per layer.
    # An entry may instead be a recurrent layer KIND: "linear", a
    # Gated-DeltaNet mixer (the lin_* sizes below), or "mamba", a Mamba-1
    # mixer (the mamba_* sizes), in the attention's place. One model holds
    # one recurrent kind.
    layer_pattern: tuple = ()
    # MoE variants. expert_act: the gate activation of the expert MLP
    # ("silu" SwiGLU | "relu" ReGLU). router_input: the tensor the router
    # scores — "mlp_norm" (the MLP-side norm, as Switch/Mixtral) or
    # "attn_norm" (the layer's normalised INPUT, i.e. the router sits
    # before attention). router_f32: the [T, E] router product in float32
    # at HIGHEST precision (near-ties of the top-k flip least).
    expert_act: str = "silu"
    router_input: str = "mlp_norm"
    router_f32: bool = False
    # ONE CHIP'S SHARE of an expert-parallel group: this program holds
    # experts expert_first .. expert_first + experts_held - 1 of every
    # layer (0 = all n_experts). The router keeps its n_experts outputs
    # and its top-k; the layer adds the held experts' part of the sum
    # (parallel.moe._moe_single_gmm) and nothing for the absent ones.
    experts_held: int = 0
    expert_first: int = 0
    # Attention kind: "gqa" (wq / wk / wv at one head width) or "latent"
    # (DeepSeek-V2/V3's multi-head latent attention): q through a rank
    # q_lora_rank bottleneck with its own RMSNorm, k and v through a shared
    # rank kv_lora_rank one; a head's q and k are qk_nope_dim wide without
    # position plus qk_rope_dim wide with rotary embedding (adjacent pairs,
    # the key's rotary part ONE per token for all heads), its v v_head_dim
    # wide; scores over sqrt(qk_nope_dim + qk_rope_dim).
    attn_kind: str = "gqa"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # The first n_dense_lead of the n_layers have a dense SwiGLU MLP of
    # width d_ff_dense where the others have experts: a section of its own
    # in front of the scanned stack (params["lead"]).
    n_dense_lead: int = 0
    d_ff_dense: int = 0
    # The router's scoring: "softmax" (the weights are the softmax's, over
    # the chosen when k > 1) or "sigmoid" — s = sigmoid(logits), the top-k
    # taken over s + b with b a per-expert BIAS that is model state and no
    # trained leaf (router_bias: it rides TrainState.extra, and the loss
    # returns b + router_bias_rate * sign(mean load - load_e)), the weights
    # s of the chosen over their sum times router_scale. router_groups is
    # the group-limited routing's group count; only 1 (no limit) runs.
    router_score: str = "softmax"
    router_bias: bool = False
    router_bias_rate: float = 0.001
    router_scale: float = 1.0
    router_groups: int = 1
    # Shared experts: a dense gated MLP of width n_shared_experts * d_ff
    # beside the routed ones, computed for every token (under a share of
    # the experts it is whole: every chip computes it for its own tokens).
    n_shared_experts: int = 0
    # Multi-token prediction (DeepSeek-V3): mtp_depth extra modules, each
    # [norm(Emb(t_{i+1})) | norm(h_i)] · W_eh -> one whole expert layer ->
    # its own final norm -> the SHARED head, scored against t_{i+2} and
    # added to the loss with weight mtp_weight. Only depth 0 and 1 run.
    mtp_depth: int = 0
    mtp_weight: float = 0.3
    # False: an output head of its own (params["head"], [vocab, d_model]).
    tied_head: bool = True
    # The "linear" layers' mixer (Gated DeltaNet, ops/gated_delta.py):
    # lin_heads heads with keys lin_dk and values lin_dv wide, a causal
    # depthwise convolution of lin_conv taps over [q | k | v] before the
    # recurrence, the write strength in [0, 2] (lin_neg_eigval) or [0, 1].
    lin_heads: int = 0
    lin_dk: int = 0
    lin_dv: int = 0
    lin_conv: int = 4
    lin_neg_eigval: bool = True
    # The "mamba" layers' mixer (Mamba-1, ops/selective_scan.py): an inner
    # width of mamba_expand * d_model channels behind a causal depthwise
    # convolution of mamba_d_conv taps (with a bias), a state of
    # mamba_d_state numbers a channel, the step size through a rank
    # mamba_dt_rank bottleneck; Jamba's RMSNorms on dt, B and C.
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    # "pre": x + F(norm(x)), the Llama order. "post": x + norm(F(x)), the
    # OLMo-2 order — the same two gains a layer, on the sublayers' OUTPUTS.
    norm_order: str = "pre"
    # RMSNorm over the whole q and the whole k projection (gains q_norm
    # [heads·head_dim], k_norm [kv_heads·head_dim]) before the heads split.
    qk_norm: bool = False

    def __post_init__(self):
        if self.n_experts and not (1 <= self.moe_top_k <= self.n_experts):
            raise ValueError(
                f"moe_top_k={self.moe_top_k} must be in [1, n_experts="
                f"{self.n_experts}] (it silently corrupts FLOP accounting "
                "and fails inside lax.top_k otherwise)"
            )
        if self.n_layers % len(self.pattern):
            raise ValueError(
                f"n_layers={self.n_layers} is not a whole number of periods "
                f"of {len(self.pattern)} layers"
            )
        if any(w and not self.causal for w, _ in self.attn_kinds):
            raise ValueError("a window layer needs causal=True")
        if self.norm_order not in ("pre", "post"):
            raise ValueError(f"unknown norm_order {self.norm_order!r}")
        if (self.norm_order == "post" or self.qk_norm) and (
                self.n_experts or self.attn_kind != "gqa"):
            raise ValueError(
                "norm_order='post' and qk_norm run on dense gqa layers only "
                "(no experts, no latent attention)")
        if LINEAR in self.pattern and (
                min(self.lin_heads, self.lin_dk, self.lin_dv) <= 0 or self.lin_conv < 2):
            raise ValueError(
                f"a 'linear' layer needs lin_heads, lin_dk, lin_dv > 0 and "
                f"lin_conv >= 2 (got {self.lin_heads}, {self.lin_dk}, "
                f"{self.lin_dv}, {self.lin_conv})")
        if MAMBA in self.pattern and (
                min(self.mamba_d_state, self.mamba_dt_rank, self.mamba_expand) <= 0
                or self.mamba_d_conv < 2):
            raise ValueError(
                f"a 'mamba' layer needs mamba_d_state, mamba_dt_rank, "
                f"mamba_expand > 0 and mamba_d_conv >= 2 (got {self.mamba_d_state}, "
                f"{self.mamba_dt_rank}, {self.mamba_expand}, {self.mamba_d_conv})")
        for kind in (k for k in RECURRENT if k in self.pattern):
            for what, bad in (
                    ("a 'linear' layer in the same model",
                     kind == MAMBA and LINEAR in self.pattern),
                    ("pipeline stages (pp_microbatches)", self.pp_microbatches),
                    (f"attn_impl={self.attn_impl!r}",
                     self.attn_impl in ("ring", "ulysses")),
                    ("experts (n_experts)", self.n_experts),
                    ("latent attention", self.attn_kind != "gqa"),
                    ("a prediction module (mtp_depth)", self.mtp_depth),
                    ("a bidirectional model (causal=False)", not self.causal)):
                if bad:
                    raise ValueError(
                        f"a {kind!r} layer does not run with {what}: its state "
                        "is carried along the whole sequence on one device")
        if self.expert_act not in ("silu", "relu"):
            raise ValueError(f"unknown expert_act {self.expert_act!r}")
        if self.router_input not in ("mlp_norm", "attn_norm"):
            raise ValueError(f"unknown router_input {self.router_input!r}")
        if self.experts_held:
            if not 0 <= self.expert_first <= self.n_experts - self.experts_held:
                raise ValueError(
                    f"experts {self.expert_first}..+{self.experts_held} are "
                    f"not among {self.n_experts}"
                )
            if self.n_held < self.n_experts and self.moe_dispatch != "gmm":
                raise ValueError(
                    "a share of the experts runs on moe_dispatch='gmm' only"
                )
        if self.attn_kind not in ("gqa", "latent"):
            raise ValueError(f"unknown attn_kind {self.attn_kind!r}")
        if self.attn_kind == "latent":
            sizes = (self.q_lora_rank, self.kv_lora_rank, self.qk_nope_dim,
                     self.qk_rope_dim, self.v_head_dim)
            if min(sizes) <= 0 or self.qk_rope_dim % 2:
                raise ValueError(
                    f"latent attention needs its five sizes (got {sizes}), "
                    "the rotary width even"
                )
            if self.attn_impl in ("ring", "ulysses"):
                raise ValueError(
                    f"latent attention runs on 'flash' or 'dense', not "
                    f"{self.attn_impl!r}"
                )
            if any(w or not r for w, r in self.attn_kinds):
                raise ValueError(
                    "latent attention runs global rotary layers only (no "
                    "window, no NoPE layer)"
                )
            if self.n_kv_heads != self.n_heads:
                raise ValueError("latent attention has one key a query head")
        if not 0 <= self.n_dense_lead <= self.n_layers:
            raise ValueError(f"n_dense_lead={self.n_dense_lead} of {self.n_layers}")
        if self.n_dense_lead and not (self.n_experts and self.d_ff_dense):
            raise ValueError(
                "leading dense layers stand in front of EXPERT layers and "
                "need their width d_ff_dense"
            )
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router_score {self.router_score!r}")
        if self.router_groups != 1:
            raise ValueError(
                f"router_groups={self.router_groups}: group-limited routing "
                "is not implemented (one group only)"
            )
        if self.router_score == "sigmoid" or self.router_bias:
            if self.moe_dispatch != "gmm" or not self.n_experts:
                raise ValueError(
                    "a sigmoid / bias-balanced router runs on experts "
                    "dispatched by moe_dispatch='gmm' only"
                )
            if self.router_bias and self.router_score != "sigmoid":
                raise ValueError("the router bias balances sigmoid scores")
        if self.mtp_depth not in (0, 1):
            raise ValueError(f"mtp_depth={self.mtp_depth}: 0 or 1 module runs")
        if self.mtp_depth and not self.causal:
            raise ValueError("multi-token prediction needs causal=True")
        if self.stack_is_new and self.pp_microbatches:
            raise ValueError(
                "leading dense layers, a shared expert, the router bias, an "
                "MTP module, an untied head, q/k norms and recurrent layers are "
                "not stage-partitioned: pp_microbatches must be 0"
            )

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def stack_is_new(self) -> bool:
        """Anything the pipelined stack does not partition."""
        return bool(self.n_dense_lead or self.n_shared_experts
                    or self.router_bias or self.mtp_depth
                    or not self.tied_head or self.attn_kind == "latent"
                    or self.qk_norm or self.recurrent_kind)

    @property
    def n_stack_layers(self) -> int:
        """Layers of the scanned stack (all of them without a dense lead)."""
        return self.n_layers - self.n_dense_lead

    @property
    def pattern(self) -> tuple:
        """One entry a layer of the period: (window, rotary), or a
        recurrent kind's name."""
        return tuple(self.layer_pattern) or ((0, True),)

    @property
    def attn_kinds(self) -> tuple:
        """The period's attention layers' (window, rotary) entries."""
        return tuple(k for k in self.pattern if kind_of(k) == ATTN)

    @property
    def recurrent_kind(self) -> Optional[str]:
        """The recurrent kind the model's pattern holds (one at most), or None."""
        return next((k for k in RECURRENT if k in self.pattern), None)

    def _in_period(self, kind: str, upto: Optional[int] = None) -> int:
        """Layers of one kind among the period's first ``upto`` (all)."""
        return sum(kind_of(k) == kind for k in self.pattern[:upto])

    def n_of_kind(self, kind) -> int:
        """How many of the stack's layers are of ``kind``, a kind's name.
        True and False still read as LINEAR and ATTN for the accepted hybrid
        runner alone (``benchmarks/runners/serve_olmo_hybrid.py``, not this
        PR's to edit): the mapping goes with the next ``benchmark`` PR."""
        kind = {True: LINEAR, False: ATTN}.get(kind, kind)
        return self._in_period(kind) * (self.n_stack_layers // len(self.pattern))

    def kind_index(self, layer: int) -> int:
        """Layer ``layer``'s place among the stack's layers of ITS kind: the
        index of its mixer's leaves (stacked by kind) and of its sequence
        state in the serve engine (pages or recurrent state)."""
        period, j = divmod(layer, len(self.pattern))
        kind = kind_of(self.pattern[j])
        return period * self._in_period(kind) + self._in_period(kind, j)

    @property
    def lin_conv_channels(self) -> int:
        """Channels the linear mixer's convolution runs over: [q | k | v]."""
        return self.lin_heads * (2 * self.lin_dk + self.lin_dv)

    @property
    def mamba_inner(self) -> int:
        """Channels of the Mamba mixer: its inner width."""
        return self.mamba_expand * self.d_model

    @property
    def n_held(self) -> int:
        """Experts whose weights this program holds."""
        return self.experts_held or self.n_experts

    def n_params(self) -> int:
        """Parameter count (for MFU accounting): what this program holds —
        the leaves of ``model_leaves``, summed."""
        return sum(math.prod(leaf.shape)
                   for leaf in jax.tree_util.tree_leaves(model_leaves(self)))

    def n_active_params(self) -> int:
        """Params touched per token (= n_params for dense; top-k MoE
        activates k experts, of which the share held / n_experts are here
        on average) — the right N for 6ND FLOP accounting."""
        if not self.n_experts:
            return self.n_params()
        d, f = self.d_model, self.d_ff
        L = self.n_stack_layers + self.mtp_depth  # every expert layer
        active = self.moe_top_k * self.n_held / self.n_experts
        return self.n_params() - int(L * (self.n_held - active) * 3 * d * f)


PRESETS: Dict[str, TransformerConfig] = {
    # test-scale
    "tiny": TransformerConfig(
        vocab=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
        max_seq=128, remat=False,
    ),
    "tiny-moe": TransformerConfig(
        vocab=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
        max_seq=128, remat=False, n_experts=4,
    ),
    "gpt-small": TransformerConfig(
        vocab=50257, d_model=768, n_layers=12, n_heads=12, n_kv_heads=12, d_ff=3072,
        max_seq=1024,
    ),
    # Mixtral-class sparse config (8 experts, top-1 routing): total params
    # ~8x the dense MLP stack, active params per token ~ the dense model.
    # r6: the grouped-matmul dispatch is the default (it beat the r4
    # capacity path at zero drops in the r5 capture).
    "moe-small": TransformerConfig(
        vocab=32000, d_model=768, n_layers=12, n_heads=12, n_kv_heads=12, d_ff=3072,
        max_seq=1024, n_experts=8, moe_dispatch="gmm",
    ),
    # BERT-base as bidirectional encoder (MLM-style head)
    "bert-base": TransformerConfig(
        vocab=30522, d_model=768, n_layers=12, n_heads=12, n_kv_heads=12, d_ff=3072,
        max_seq=512, causal=False,
    ),
    # North-star-shape single-chip config: the largest GQA model whose
    # adamw state fits one 16 GB chip, at the d>=2048 shapes the 50%-MFU
    # target presumes (at gpt-small's d=768 the per-op overhead of the
    # model, not the matmul rate, holds the step back). ~795M params —
    # sized against the MEASURED adamw residency of ~18 bytes/param at
    # grad_accum=1 (p+m+v+grads f32
    # + the bf16 compute cast; accum>1 adds a second f32 grad buffer and
    # pushed the L=14 variant to 19.9G on a 15.75G chip). The
    # [b·t,2048]x[2048,8192] MLP matmuls dominate the FLOPs.
    "gqa-2048": TransformerConfig(
        vocab=32000, d_model=2048, n_layers=12, n_heads=16, n_kv_heads=4,
        d_ff=8192, max_seq=4096,
    ),
    "llama2-7b": TransformerConfig(
        vocab=32000, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=32, d_ff=11008,
        max_seq=4096,
    ),
    "llama2-13b": TransformerConfig(
        vocab=32000, d_model=5120, n_layers=40, n_heads=40, n_kv_heads=40, d_ff=13824,
        max_seq=4096,
    ),
    # The GQA member of the family (8 kv heads vs 64 query heads): the
    # config that actually exercises grouped-query attention at scale.
    # Memory plan validated by tests/test_tools.py::TestMemPlan (fits a
    # v5p-256-shaped fsdp=32 x tp=8 mesh).
    "llama2-70b": TransformerConfig(
        vocab=32000, d_model=8192, n_layers=80, n_heads=64, n_kv_heads=8, d_ff=28672,
        max_seq=4096,
    ),
    # Flagship-scale sparse config: Mixtral-8x7B
    # shapes — 8 experts top-2, GQA 32q/8kv, ~46.5B total / ~12.7B
    # active params. Its legal mesh is dp x fsdp x ep: experts shard
    # over ep on their expert dim AND over fsdp on their embed dim
    # (DEFAULT_RULES "expert"/"embed"), so expert weights no longer
    # replicate per dp replica — the memplan-closing layout for a
    # v5p-256 pod (examples/mixtral_8x7b_v5p256.json). r6: the default
    # dispatch is the padding-free grouped-matmul kernel — it now runs
    # UNDER the ep axis (count-exchange + block-quantum a2a buffers), so
    # the flagship no longer pays cf× padding FLOPs or drops tokens.
    "mixtral-8x7b": TransformerConfig(
        vocab=32000, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        d_ff=14336, max_seq=4096, n_experts=8, moe_top_k=2,
        moe_dispatch="gmm",
    ),
    # SmallThinker-21BA3B-Instruct (PowerInfer; config.json on the hub):
    # period [global + NoPE, window 4096 + rotary x3] x 13, 28 query / 4 KV
    # heads of width 128 (28·128 = 3584 != d_model), a top-6 router over 64
    # ReGLU experts of width 768 placed BEFORE attention, no shared expert.
    # One chip trains a SHARE of it (experts_held / n_layers / vocab
    # overrides: benchmarks/configs/smallthinker-21ba3b-ep4share-train1.json).
    "smallthinker-21ba3b": TransformerConfig(
        vocab=151936, d_model=2560, n_layers=52, n_heads=28, n_kv_heads=4,
        d_head=128, d_ff=768, max_seq=16384, rope_theta=1.5e6, norm_eps=1e-6,
        n_experts=64, moe_top_k=6, moe_dispatch="gmm",
        layer_pattern=((0, False), (4096, True), (4096, True), (4096, True)),
        expert_act="relu", router_input="attn_norm", router_f32=True,
    ),
    # JoyAI-LLM-Flash (jdopensource; config.json on the hub; 48B-A2.7B):
    # latent attention (32 heads, q/k 128 + 64 rotary = 192 wide, v 128,
    # ranks 1536 / 512), one leading dense layer of width 7168 before 39
    # expert layers — a sigmoid top-8 router over 256 SwiGLU experts of
    # width 768, balanced by a bias, scale 2.5, beside one shared expert —
    # one multi-token-prediction module, an untied head. One chip trains a
    # SHARE of it (benchmarks/configs/joyai-llm-flash-ep16share-train1.json).
    "joyai-llm-flash": TransformerConfig(
        vocab=129280, d_model=2048, n_layers=40, n_heads=32, n_kv_heads=32,
        d_ff=768, max_seq=131072, rope_theta=3.2e7, norm_eps=1e-6,
        attn_kind="latent", q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
        n_dense_lead=1, d_ff_dense=7168,
        n_experts=256, moe_top_k=8, moe_dispatch="gmm", router_f32=True,
        router_score="sigmoid", router_bias=True, router_bias_rate=0.001,
        router_scale=2.5, n_shared_experts=1,
        moe_aux_weight=0.0, moe_zloss_weight=0.0,
        mtp_depth=1, mtp_weight=0.3, tied_head=False,
    ),
    # Olmo-Hybrid-7B (allenai; config.json on the hub, model_type
    # olmo_hybrid): period [Gated-DeltaNet linear x3, full attention] x 8;
    # the linear mixer has 30 heads with keys 96 and values 192 wide behind
    # a 4-tap convolution, write strengths in [0, 2]; the full layers 30
    # heads = 30 KV heads of 128 with NO rotary embedding; SwiGLU 11008; an
    # untied head. By the OLMo-2/3 family's convention (the config has no
    # key for it) the norms stand on each sublayer's output and q and k are
    # normalised whole. One chip SERVES two periods of it
    # (benchmarks/configs/olmo-hybrid-7b-serve1.json).
    "olmo-hybrid-7b": TransformerConfig(
        vocab=100352, d_model=3840, n_layers=32, n_heads=30, n_kv_heads=30,
        d_ff=11008, max_seq=65536, norm_eps=1e-6,
        layer_pattern=(LINEAR, LINEAR, LINEAR, (0, False)),
        lin_heads=30, lin_dk=96, lin_dv=192, lin_conv=4, lin_neg_eigval=True,
        norm_order="post", qk_norm=True, tied_head=False,
    ),
    # AI21-Jamba2-3B (ai21labs; config.json on the hub, model_type jamba):
    # 28 layers, layer i attends iff i % 14 == 7, every other layer is a
    # Mamba-1 mixer (inner width 5120, state 16, 4 taps with a bias, dt rank
    # 160, RMSNorms on dt, B and C); attention is 20 query heads over ONE
    # key/value head of 128 with NO rotary embedding; every feed-forward the
    # SiLU-gated MLP (num_experts 1); pre-norm, a tied head. One chip SERVES
    # all of it (benchmarks/configs/ai21-jamba2-3b-serve1.json).
    "ai21-jamba2-3b": TransformerConfig(
        vocab=65536, d_model=2560, n_layers=28, n_heads=20, n_kv_heads=1,
        d_ff=8192, max_seq=262144, norm_eps=1e-6,
        layer_pattern=(MAMBA,) * 7 + ((0, False),) + (MAMBA,) * 6,
        mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=160,
    ),
}


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Leaf:
    """One weight of the model, said ONCE: ``init_transformer``,
    ``transformer_logical_axes``, ``TransformerConfig.n_params``, the
    pipeline's stage specs and the by-kind slicing of a period's layers all
    read these rows. Adding a weight is adding a row."""

    name: str
    shape: tuple  # a stacked leaf's first dimension: the layers that hold it
    axes: tuple  # a logical axis (parallel.sharding) or None a dimension
    # the steps from the model's key to this leaf's, each ("split", n, i) —
    # split(key, n)[i] — or ("fold", i) — fold_in(key, i); () draws nothing
    key: tuple = ()
    draw: Callable = lambda key, shape: jnp.ones(shape, jnp.float32)
    # the layer KIND that stacks it: ATTN (the layers that attend), LINEAR,
    # MAMBA, or None (every layer)
    kind: Optional[str] = None


def _normal(scale: float) -> Callable:
    return lambda key, shape: jax.random.normal(key, shape, jnp.float32) * scale


# The decay's two per-head scalars as the published Gated-DeltaNet layer
# initialises them (A uniform in [1, 16), the step dt log-uniform in [1e-3,
# 1e-1) and stored through the inverse softplus), so that a state remembers
# tens to hundreds of tokens, as a trained one does.


def _a_log(key, shape):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _dt_bias(key, shape):
    dt = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _zeros(key, shape):
    return jnp.zeros(shape, jnp.float32)


def _state_a_log(key, shape):
    """Mamba-1's A as the published layer constructs it: 1 .. N a channel."""
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape)


def layer_leaves(cfg: TransformerConfig, n_layers: int, dense: bool) -> List[Leaf]:
    """The leaves of ``n_layers`` stacked layers of one shape: dense MLPs (of
    width d_ff_dense when the model also has experts: its leading section)
    or expert layers. A mixer's leaves are stacked by KIND: the attention's
    over the layers that attend, the linear mixer's (lin_*) over the linear
    ones, the Mamba mixer's (mamba_*) over the Mamba ones. Matrices are
    normal draws scaled fan-in^-1/2. The leaves the first models had draw
    keys 0..7 of the section key's split, as they always did; the latent
    attention's and the shared expert's draw from a second split
    (``fold_in(key, 8)``), the linear mixer's from a third (9), the Mamba
    mixer's from a fourth (10)."""
    d = cfg.d_model
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    n_lin, n_mamba = cfg.n_of_kind(LINEAR), cfg.n_of_kind(MAMBA)
    stacked = {None: n_layers, ATTN: n_layers - n_lin - n_mamba, LINEAR: n_lin,
               MAMBA: n_mamba}
    first, second, third, fourth = (), (("fold", 8),), (("fold", 9),), (("fold", 10),)

    def gain(name, width, axis, kind=None):
        return Leaf(name, (stacked[kind], width), ("layers", axis), kind=kind)

    def drawn(name, split, i, draw, dims, axes, kind=None):
        return Leaf(name, (stacked[kind],) + dims, ("layers",) + axes,
                    split + (("split", 8, i),), draw, kind)

    def matrix(name, split, i, fan_in, dims, axes, kind=None):
        return drawn(name, split, i, _normal(fan_in**-0.5), dims, axes, kind)

    def gated_mlp(prefix, split, f, experts=(), axis=()):
        """Gate, up and down of one width: keys 4, 5, 6 of their split."""
        return [
            matrix(prefix + "_gate", split, 4, d, experts + (d, f), axis + ("embed", "mlp")),
            matrix(prefix + "_up", split, 5, d, experts + (d, f), axis + ("embed", "mlp")),
            matrix(prefix + "_down", split, 6, f, experts + (f, d), axis + ("mlp", "embed")),
        ]

    leaves = [gain("attn_norm", d, "embed")]
    if n_lin:
        H, K = cfg.lin_heads, cfg.lin_conv
        ch, hv = cfg.lin_conv_channels, cfg.lin_heads * cfg.lin_dv
        leaves += [
            matrix("lin_wqkv", third, 0, d, (d, ch), ("embed", "heads"), LINEAR),
            matrix("lin_wz", third, 1, d, (d, hv), ("embed", "heads"), LINEAR),
            matrix("lin_wba", third, 2, d, (d, 2 * H), ("embed", None), LINEAR),
            matrix("lin_conv", third, 3, K, (K, ch), (None, "heads"), LINEAR),
            matrix("lin_wo", third, 4, hv, (hv, d), ("heads", "embed"), LINEAR),
            drawn("lin_A_log", third, 5, _a_log, (H,), (None,), LINEAR),
            drawn("lin_dt_bias", third, 6, _dt_bias, (H,), (None,), LINEAR),
            gain("lin_norm", cfg.lin_dv, None, LINEAR),
        ]
    if n_mamba:
        inner, N, R, K = (cfg.mamba_inner, cfg.mamba_d_state, cfg.mamba_dt_rank,
                          cfg.mamba_d_conv)
        leaves += [
            matrix("mamba_in", fourth, 0, d, (d, 2 * inner), ("embed", "heads"), MAMBA),
            matrix("mamba_conv", fourth, 1, K, (K, inner), (None, "heads"), MAMBA),
            Leaf("mamba_conv_bias", (n_mamba, inner), ("layers", "heads"),
                 draw=_zeros, kind=MAMBA),
            matrix("mamba_x", fourth, 2, inner, (inner, R + 2 * N), ("heads", None), MAMBA),
            matrix("mamba_dt", fourth, 3, R, (R, inner), (None, "heads"), MAMBA),
            drawn("mamba_dt_bias", fourth, 4, _dt_bias, (inner,), ("heads",), MAMBA),
            Leaf("mamba_A_log", (n_mamba, inner, N), ("layers", "heads", None),
                 draw=_state_a_log, kind=MAMBA),
            gain("mamba_D", inner, "heads", MAMBA),
            matrix("mamba_out", fourth, 5, inner, (inner, d), ("heads", "embed"), MAMBA),
            gain("mamba_dt_norm", R, None, MAMBA),
            gain("mamba_b_norm", N, None, MAMBA),
            gain("mamba_c_norm", N, None, MAMBA),
        ]
    if cfg.attn_kind == "latent":
        # the low-rank dims stay whole; the per-head dims shard as heads
        qr, kvr, rope = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_dim
        qk, kv, v = cfg.qk_nope_dim + rope, cfg.qk_nope_dim + cfg.v_head_dim, cfg.v_head_dim
        leaves += [
            matrix("wq_a", second, 0, d, (d, qr), ("embed", None), ATTN),
            gain("q_norm", qr, None, ATTN),
            matrix("wq_b", second, 1, qr, (qr, nh * qk), (None, "heads"), ATTN),
            matrix("wkv_a", second, 2, d, (d, kvr + rope), ("embed", None), ATTN),
            gain("kv_norm", kvr, None, ATTN),
            matrix("wkv_b", second, 3, kvr, (kvr, nh * kv), (None, "heads"), ATTN),
            matrix("wo", first, 3, nh * v, (nh * v, d), ("heads", "embed"), ATTN),
        ]
    elif stacked[ATTN]:
        leaves += [
            matrix("wq", first, 0, d, (d, nh * hd), ("embed", "heads"), ATTN),
            matrix("wk", first, 1, d, (d, nkv * hd), ("embed", "kv_heads"), ATTN),
            matrix("wv", first, 2, d, (d, nkv * hd), ("embed", "kv_heads"), ATTN),
            matrix("wo", first, 3, nh * hd, (nh * hd, d), ("heads", "embed"), ATTN),
        ]
        if cfg.qk_norm:
            leaves += [gain("q_norm", nh * hd, "heads", ATTN),
                       gain("k_norm", nkv * hd, "kv_heads", ATTN)]
    leaves.append(gain("mlp_norm", d, "embed"))
    if cfg.n_experts and not dense:
        # the router scores all n_experts, the weights are the held
        leaves.append(matrix("w_router", first, 7, d, (d, cfg.n_experts), ("embed", "expert")))
        leaves += gated_mlp("w", first, cfg.d_ff, (cfg.n_held,), ("expert",))
        if cfg.n_shared_experts:
            leaves += gated_mlp("ws", second, cfg.n_shared_experts * cfg.d_ff)
    else:
        leaves += gated_mlp("w", first, cfg.d_ff_dense if dense and cfg.n_experts else cfg.d_ff)
    return leaves


def model_leaves(cfg: TransformerConfig) -> Dict[str, Any]:
    """The parameter tree with a ``Leaf`` at every leaf: the top level's own
    rows and one ``layer_leaves`` section each for ``layers`` (all of them,
    or the expert layers behind a dense lead), ``lead`` (the leading dense
    layers), ``mtp.layer`` (the multi-token-prediction module's one layer,
    stacked [1]); ``head`` is an untied output head."""
    d = cfg.d_model

    def section(n_layers, dense, *key):
        return {leaf.name: replace(leaf, key=key + leaf.key if leaf.key else ())
                for leaf in layer_leaves(cfg, n_layers, dense)}

    def table(name, *key):
        return Leaf(name, (cfg.vocab, d), ("vocab", "embed"), key, _normal(0.02))

    tree = {
        "embed": table("embed", ("split", 2, 0)),
        "final_norm": Leaf("final_norm", (d,), ("embed",)),
        "layers": section(cfg.n_stack_layers, False, ("split", 2, 1)),
    }
    if cfg.n_dense_lead:
        tree["lead"] = section(cfg.n_dense_lead, True, ("fold", 1))
    if cfg.mtp_depth:
        tree["mtp"] = {
            "norm_e": Leaf("norm_e", (d,), ("embed",)),
            "norm_h": Leaf("norm_h", (d,), ("embed",)),
            "w_eh": Leaf("w_eh", (2 * d, d), (None, "embed"),
                         (("fold", 2), ("fold", 0)), _normal((2 * d) ** -0.5)),
            "layer": section(1, not cfg.n_experts, ("fold", 2), ("fold", 1)),
            "final_norm": Leaf("final_norm", (d,), ("embed",)),
        }
    if not cfg.tied_head:
        tree["head"] = table("head", ("fold", 3))
    return tree


def stacked_by(cfg: TransformerConfig) -> Dict[str, Optional[str]]:
    """Each leaf of the scanned stack -> the layer kind that stacks it."""
    return {leaf.name: leaf.kind
            for leaf in layer_leaves(cfg, cfg.n_stack_layers, False)}


def init_transformer(key, cfg: TransformerConfig) -> Dict[str, Any]:
    """Initialize params (f32): every leaf of ``model_leaves`` drawn from
    its own key. Layer params are stacked on a leading [layers] axis for
    the scan."""

    @cache
    def split(steps, n):  # made once for all its indices: the init program stays small
        return jax.random.split(key_at(steps), n)

    @cache
    def key_at(steps):
        if not steps:
            return key
        op, *arg = steps[-1]
        if op == "fold":
            return jax.random.fold_in(key_at(steps[:-1]), arg[0])
        return split(steps[:-1], arg[0])[arg[1]]

    return jax.tree_util.tree_map(
        lambda leaf: leaf.draw(key_at(leaf.key), leaf.shape), model_leaves(cfg))


def transformer_logical_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    """Logical axis names per param leaf (same tree structure as params)."""
    return jax.tree_util.tree_map(lambda leaf: leaf.axes, model_leaves(cfg))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _rms_norm(x, gamma, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gamma.astype(
        x.dtype
    )


def _rope(x, theta: float):
    """Rotary position embedding. x: [b, t, h, d_head]."""
    b, t, h, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]  # [t, half]
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _rope_pairs(x, theta: float):
    """Rotary embedding over ADJACENT pairs (x[2i], x[2i+1]) — the layout
    of a ``rope_interleave`` checkpoint. x: [b, t, h, d]. The rotated pairs
    leave de-interleaved (all first members, then all second): q and k go
    through the same permutation, which no score sees."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    pairs = x.reshape(x.shape[:-1] + (half, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _latent_qkv(h, layer_params, cfg: TransformerConfig):
    """Latent attention's projections: h [b, t, d] -> q, k [b, t, nh,
    nope + rope], v [b, t, nh, v_head_dim]. q goes through the rank
    q_lora_rank bottleneck and its RMSNorm; k's position-free part and v
    come up from the shared rank kv_lora_rank latent (normed); the rotary
    part of k is ONE [b, t, rope] vector a token, read beside the latent
    from the same projection and repeated for every head."""
    b, t, _ = h.shape
    nh, nope, rope = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    dt = h.dtype
    c_q = _rms_norm(h @ layer_params["wq_a"].astype(dt),
                    layer_params["q_norm"], cfg.norm_eps)
    q = (c_q @ layer_params["wq_b"].astype(dt)).reshape(b, t, nh, nope + rope)
    ckv = h @ layer_params["wkv_a"].astype(dt)  # [b, t, kv_rank | rope]
    c_kv = _rms_norm(ckv[..., :cfg.kv_lora_rank], layer_params["kv_norm"],
                     cfg.norm_eps)
    k_r = _rope_pairs(ckv[..., None, cfg.kv_lora_rank:], cfg.rope_theta)
    kv = (c_kv @ layer_params["wkv_b"].astype(dt)).reshape(
        b, t, nh, nope + cfg.v_head_dim)
    q = jnp.concatenate(
        [q[..., :nope], _rope_pairs(q[..., nope:], cfg.rope_theta)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, t, nh, rope))], axis=-1)
    return q, k, kv[..., nope:]


def rope_at_positions(x, positions, theta: float):
    """_rope at explicit ABSOLUTE positions. x: [b, t, h, d_head];
    positions: [b, t] int. ``_rope(x, theta)`` is exactly this with
    positions = arange(t) — the incremental decode path (serve/engine.py)
    needs the general form because a decode step's single token sits at
    position seq_len, not 0, and a prefill chunk starts mid-sequence;
    rotating at the wrong absolute position is the classic silent KV-cache
    bug (every token attends as if it were the first)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [b, t, half]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# The linear (Gated DeltaNet) mixer in pieces the serve engine shares: it
# runs the same projections and gates around its OWN convolution tail and
# recurrent state (serve/engine.py), where the whole-sequence forward below
# starts both from zero.


def lin_project(h, lp, cfg: TransformerConfig):
    """h [..., d] -> (the convolution's input [..., channels] = [q | k | v]
    before the taps, the output gate z, the write-strength logit b and the
    decay logit a, both [..., H])."""
    dt = h.dtype
    ba = h @ lp["lin_wba"].astype(dt)
    H = cfg.lin_heads
    return (h @ lp["lin_wqkv"].astype(dt), h @ lp["lin_wz"].astype(dt),
            ba[..., :H], ba[..., H:])


def lin_conv_taps(ext, w, n: int, bias=None):
    """The causal depthwise convolution + SiLU over ``n`` positions: ``ext``
    [..., n + K − 1, channels] is the input with the K − 1 positions before
    it in front (zeros at a sequence's start), w [K, channels] the taps, tap
    j on the input j − (K − 1) positions back; ``bias`` [channels] (the Mamba
    mixer's) is added before the SiLU."""
    K = w.shape[0]
    acc = sum(ext[..., j:j + n, :] * w[j].astype(ext.dtype) for j in range(K))
    if bias is not None:
        acc = acc + bias.astype(ext.dtype)
    return jax.nn.silu(acc)


def lin_gates(u, b, a, lp, cfg: TransformerConfig):
    """The recurrence's operands from the convolved channels u [..., ch] and
    the two logits [..., H]: q (l2-normalised, scaled d_k^-1/2), k
    (l2-normalised) [..., H, d_k], v [..., H, d_v], alpha_log = −exp(A_log) ·
    softplus(a + dt_bias) and beta = sigmoid(b), doubled where the model
    lets the transition's eigenvalues turn negative — all float32."""
    H, dk, dv = cfg.lin_heads, cfg.lin_dk, cfg.lin_dv
    u = u.astype(jnp.float32)
    lead = u.shape[:-1]

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    q = l2(u[..., : H * dk].reshape(lead + (H, dk))) * dk**-0.5
    k = l2(u[..., H * dk: 2 * H * dk].reshape(lead + (H, dk)))
    v = u[..., 2 * H * dk:].reshape(lead + (H, dv))
    alpha_log = -jnp.exp(lp["lin_A_log"]) * jax.nn.softplus(
        a.astype(jnp.float32) + lp["lin_dt_bias"])
    beta = jax.nn.sigmoid(b.astype(jnp.float32)) * (2.0 if cfg.lin_neg_eigval else 1.0)
    return q, k, v, alpha_log, beta


def lin_output(o, z, lp, cfg: TransformerConfig, dtype):
    """(RMSNorm over d_v of the recurrence's output, one gain for all
    heads) ⊙ SiLU(z), through the output projection: [..., H, d_v] -> [..., d]."""
    o = _rms_norm(o, lp["lin_norm"], cfg.norm_eps).astype(dtype)
    y = o * jax.nn.silu(z.reshape(o.shape))
    return y.reshape(y.shape[:-2] + (-1,)) @ lp["lin_wo"].astype(dtype)


def _linear_mixer(h, lp, cfg: TransformerConfig):
    """Whole sequences [b, t, d] through the linear mixer, from a zero
    convolution tail and a zero state: the chunked scan, a row at a time."""
    from tf_operator_tpu.ops.gated_delta import gated_delta_chunk

    t = h.shape[1]
    pre, z, b, a = lin_project(h, lp, cfg)
    ext = jnp.pad(pre, ((0, 0), (cfg.lin_conv - 1, 0), (0, 0)))
    q, k, v, alpha_log, beta = lin_gates(
        lin_conv_taps(ext, lp["lin_conv"], t), b, a, lp, cfg)
    state0 = jnp.zeros((cfg.lin_heads, cfg.lin_dk, cfg.lin_dv), jnp.float32)
    o, _ = jax.vmap(
        lambda *row: gated_delta_chunk(*row, state0))(q, k, v, alpha_log, beta)
    return lin_output(o, z, lp, cfg, h.dtype)


# The Mamba-1 mixer in the same pieces (the convolution is ``lin_conv_taps``
# with the mixer's bias): the serve engine runs them around its own
# convolution tail and state, the whole-sequence forward from zeros.


def mamba_project(h, lp, cfg: TransformerConfig):
    """h [..., d] -> (the convolution's input x [..., inner] before the taps,
    the output gate z [..., inner])."""
    xz = h @ lp["mamba_in"].astype(h.dtype)
    return xz[..., : cfg.mamba_inner], xz[..., cfg.mamba_inner:]


def mamba_gates(u, lp, cfg: TransformerConfig):
    """The recurrence's operands from the convolved channels u [..., inner]:
    the step size delta = softplus(RMSNorm(dt) · W_dt + b_dt) [..., inner], B
    and C [..., N] (each through its RMSNorm: Jamba's three inner norms), and
    A = −exp(A_log) [inner, N] — all float32."""
    R, N = cfg.mamba_dt_rank, cfg.mamba_d_state
    dbc = (u @ lp["mamba_x"].astype(u.dtype)).astype(jnp.float32)
    dt = _rms_norm(dbc[..., :R], lp["mamba_dt_norm"], cfg.norm_eps)
    B = _rms_norm(dbc[..., R:R + N], lp["mamba_b_norm"], cfg.norm_eps)
    C = _rms_norm(dbc[..., R + N:], lp["mamba_c_norm"], cfg.norm_eps)
    delta = jax.nn.softplus(dt @ lp["mamba_dt"] + lp["mamba_dt_bias"])
    return delta, B, C, -jnp.exp(lp["mamba_A_log"])


def mamba_output(y, z, lp, dtype):
    """(the recurrence's output ⊙ SiLU(z)) through the output projection:
    [..., inner] -> [..., d]."""
    return (y.astype(dtype) * jax.nn.silu(z)) @ lp["mamba_out"].astype(dtype)


def _mamba_mixer(h, lp, cfg: TransformerConfig):
    """Whole sequences [b, t, d] through the Mamba mixer, from a zero
    convolution tail and a zero state: the scan, a row at a time."""
    from tf_operator_tpu.ops.selective_scan import selective_scan_chunk

    t = h.shape[1]
    x, z = mamba_project(h, lp, cfg)
    ext = jnp.pad(x, ((0, 0), (cfg.mamba_d_conv - 1, 0), (0, 0)))
    u = lin_conv_taps(ext, lp["mamba_conv"], t, lp["mamba_conv_bias"])
    delta, B, C, A = mamba_gates(u, lp, cfg)
    state0 = jnp.zeros((cfg.mamba_d_state, cfg.mamba_inner), jnp.float32)
    y, _ = jax.vmap(lambda *row: selective_scan_chunk(
        *row, A, lp["mamba_D"], state0))(u, delta, B, C)
    return mamba_output(y, z, lp, h.dtype)


def _attention(q, k, v, cfg: TransformerConfig, mesh, window: int = 0):
    """q: [b,t,nh,hd]; k/v: [b,t,nkv,hd]. ``window`` > 0: a sliding-window
    layer (flash and dense paths; ring / ulysses have no window).

    GQA (nkv < nh) runs NATIVE on the dense, flash AND ring paths: no
    [b,t,nh,hd] K/V tensor ever exists — the flash kernel grids over K/V
    heads with the group folded into its q tile ([g·block_q, hd] rows
    per K/V block load, so in-kernel K/V HBM traffic scales with nkv),
    the dense path groups the einsum
    (ops/flash_attention.py), and ring attention rotates the SMALL
    [*, nkv, hd] blocks around the cp ring (g-times less ICI traffic per
    hop — parallel/ring_attention.py), keeping K/V traffic at the nkv
    rate that is GQA's whole point at t>=4096. Ulysses is GQA-native when
    n_kv % cp == 0 (K/V all-to-all on their own smaller head dim); with
    indivisible kv counts it all-gathers the small K/V over cp and
    head-maps per shard (r4 — no repeated [t, h, hd] tensor either way),
    both handled inside parallel/ulysses.py."""
    if window and cfg.attn_impl in ("ring", "ulysses"):
        raise NotImplementedError(
            f"attn_impl={cfg.attn_impl!r} has no sliding window; window "
            "layers run on 'flash' or 'dense'"
        )
    if cfg.attn_impl == "ring" and mesh is not None and AXIS_CONTEXT in mesh.axis_names:
        from tf_operator_tpu.parallel.ring_attention import ring_attention

        batch_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names) or None
        return ring_attention(
            q, k, v, mesh, axis_name=AXIS_CONTEXT, causal=cfg.causal, batch_axes=batch_axes
        )
    if cfg.attn_impl == "ulysses" and mesh is not None and AXIS_CONTEXT in mesh.axis_names:
        # All-to-all SP (DeepSpeed-Ulysses): re-shard seq->heads once, run
        # ordinary full-sequence attention per head shard (the flash kernel
        # applies untouched on TPU; dense fallback elsewhere), re-shard back.
        from tf_operator_tpu.ops.flash_attention import flash_attention
        from tf_operator_tpu.parallel.ulysses import ulysses_attention

        batch_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names) or None
        return ulysses_attention(
            q, k, v, mesh, axis_name=AXIS_CONTEXT, causal=cfg.causal,
            batch_axes=batch_axes,
            attn_fn=lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=cfg.causal),
        )
    if cfg.attn_impl == "flash":
        from tf_operator_tpu.ops.flash_attention import flash_attention

        # Pallas online-softmax kernel on TPU; identical-math jnp fallback
        # elsewhere, so one config runs on the CPU test mesh too. Under a
        # mesh the pallas_call has no GSPMD partitioning rule, so wrap in
        # shard_map — attention is independent per (batch, head), so batch
        # shards over dp/fsdp and heads over tp with no collectives. A
        # sequence-sharded (cp) mesh needs ring attention instead.
        if mesh is not None and mesh.devices.size > 1:
            from tf_operator_tpu.parallel.collectives import shard_map
            from jax.sharding import PartitionSpec as P

            batch = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names) or None
            heads = "tp" if "tp" in mesh.axis_names else None
            tp = mesh.shape["tp"] if heads else 1
            if k.shape[2] % tp:
                # kv heads don't divide tp (tiny test configs): materialize
                # the repeat so head sharding stays legal. When nkv % tp
                # == 0 (llama2-70b: 8 kv / tp=8) GQA stays native: the
                # per-shard contiguous head blocks keep hi//g mapping to
                # the right local kv head (g_local == g).
                grp = q.shape[2] // k.shape[2]
                k = jnp.repeat(k, grp, axis=2)
                v = jnp.repeat(v, grp, axis=2)
            spec = P(batch, None, heads, None)
            fn = shard_map(
                lambda q, k, v: flash_attention(q, k, v, causal=cfg.causal,
                                                window=window),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
            )
            return fn(q, k, v)
        return flash_attention(q, k, v, causal=cfg.causal, window=window)
    # dense path: the GQA-native grouped einsum with f32 MXU accumulation
    # (ops/flash_attention.reference_attention — also the flash oracle, so
    # dense and flash configs are pinned to the same math by its tests)
    from tf_operator_tpu.ops.flash_attention import reference_attention

    # under the flash entries' name for the attention output, so that a
    # ``*_mid`` remat tier cuts the recompute chain here on this path too
    return checkpoint_name(
        reference_attention(q, k, v, causal=cfg.causal, window=window),
        "flash_o")


def _anchored_gamma(gamma, cfg: TransformerConfig, mesh):
    """Read an rms-norm gamma through a replicated constraint on MoE
    multi-axis meshes. ZeRO shards even the [d] norm scales over fsdp —
    on the dp×fsdp×ep mesh that is a TRANSPOSED tile assignment, and the
    broadcast multiply pulls the (batch-anchored) layer-scan carry and
    its backward cotangent toward that d-over-fsdp layout; GSPMD can
    only reconcile differently ORDERED assignments with an involuntary
    full rematerialization of the carry, once per layer per step. A [d]
    all-gather is noise; the carry remat is not. No-op for dense configs
    and single-axis meshes (propagation is already consistent there),
    and for pipeline/shard_map callers (mesh is None inside the stage
    body — manual axes can't take auto sharding constraints anyway)."""
    if not (cfg.n_experts and mesh is not None
            and getattr(mesh, "devices", None) is not None
            and AXIS_EXPERT in getattr(mesh, "axis_names", ())):
        return gamma
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.lax.with_sharding_constraint(
        gamma, NamedSharding(mesh, P(*(None,) * gamma.ndim))
    )


def _layer(x, layer_params, cfg: TransformerConfig, mesh, tp_axis=None,
           tp_manual_vjp=True, bound_ep: Optional[str] = None,
           kind: tuple = (0, True), dense: bool = False):
    """One decoder layer. ``kind`` = (window, rotary): this layer's entry
    of cfg.pattern, static. ``dense``: a dense-MLP layer of a model that
    has experts (its leading section), static too. ``tp_axis`` (pipeline tp-within-stage, r3):
    weights arrive as tp-LOCAL shards (wq/wk/wv/w_gate/w_up
    column-parallel, wo/w_down row-parallel — the Megatron split).

    The tp collective convention depends on WHO differentiates
    (``tp_manual_vjp``): under direct jax.vjp inside the 1F1B backward,
    plain psum is silently wrong (its transpose-is-psum convention
    inflates every cotangent behind it by tp, compounding per layer), so
    activations route through the Megatron f/g conjugate pair
    (collectives.tp_region_enter/exit). Under shard_map AUTODIFF (the
    GPipe schedule), the framework hands each tp shard gy/tp for a
    replicated output — there raw psum's transpose restores exactly the
    full cotangent and the f/g pair would HALVE row-parallel weight
    grads. Both pinned by test_pipeline_tp_grads_match_single_device.
    Head counts derive from the local weight shapes, so the same body
    serves both layouts."""
    if tp_axis is not None:
        from tf_operator_tpu.parallel.collectives import (
            tp_region_enter,
            tp_region_exit,
        )

        if tp_manual_vjp:
            enter = lambda a: tp_region_enter(a, tp_axis)  # noqa: E731
            leave = lambda a: tp_region_exit(a, tp_axis)  # noqa: E731
        else:
            enter = lambda a: a  # noqa: E731
            leave = lambda a: jax.lax.psum(a, tp_axis)  # noqa: E731
    b, t, d = x.shape
    hd = cfg.head_dim
    latent = cfg.attn_kind == "latent"
    linear = kind in RECURRENT  # no q/k/v of the attention's: the mixer projects
    post = cfg.norm_order == "post"
    # SECTION scopes (``sec_*``, PERF.md §3): metadata on the instructions
    # made here, nothing else — ``compiled_sections`` reads them back from
    # the compiled step. The statements keep the order they had.
    with jax.named_scope("sec_attn_proj"):
        if not latent and not linear:
            wq = layer_params["wq"].astype(x.dtype)
            wk = layer_params["wk"].astype(x.dtype)
            wv = layer_params["wv"].astype(x.dtype)
        gamma_attn = _anchored_gamma(layer_params["attn_norm"], cfg, mesh)
    with jax.named_scope("sec_mlp"):
        gamma_mlp = _anchored_gamma(layer_params["mlp_norm"], cfg, mesh)

    def anchor_tokens(a):
        # companion to _anchored_gamma (same scope): keeps the normed
        # activations — and, through the constraint's transpose, their
        # COTANGENTS arriving from the ZeRO-sharded qkv/router matmul
        # transposes — in the batch layout the layer-scan carry is
        # pinned to, so no d-over-fsdp pressure reaches the while
        # boundary
        if not (cfg.n_experts and mesh is not None
                and getattr(mesh, "devices", None) is not None
                and AXIS_EXPERT in getattr(mesh, "axis_names", ())):
            return a
        data_axes = tuple(ax for ax in ("dp", "fsdp") if ax in mesh.axis_names)
        if not data_axes:
            return a
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, P(data_axes, *(None,) * (a.ndim - 1)))
        )

    with jax.named_scope("sec_attn_proj"):
        # norm_order "post": the sublayer reads the stream as it is and its
        # OUTPUT is normalised (the same gain) on its way back into it
        h = x if post else anchor_tokens(_rms_norm(x, gamma_attn, cfg.norm_eps))
        if tp_axis is not None:
            h = enter(h)
        if not linear:
            window, rotary = kind
        if latent:
            q, k, v = _latent_qkv(h, layer_params, cfg)
        elif not linear:
            q, k = h @ wq, h @ wk
            if cfg.qk_norm:
                q = _rms_norm(q, layer_params["q_norm"], cfg.norm_eps)
                k = _rms_norm(k, layer_params["k_norm"], cfg.norm_eps)
            q = q.reshape(b, t, wq.shape[-1] // hd, hd)
            k = k.reshape(b, t, wk.shape[-1] // hd, hd)
            v = (h @ wv).reshape(b, t, wv.shape[-1] // hd, hd)
            if rotary:
                q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    gate_logits = None
    if cfg.n_experts and not dense and cfg.router_input == "attn_norm":
        # the router sits BEFORE attention: it scores the same normalised
        # tensor attention reads
        with jax.named_scope("sec_router"):
            gate_logits = _router_logits(h, layer_params, cfg)
    with jax.named_scope("sec_attn_core"):
        if linear:
            mixer = _linear_mixer if kind == LINEAR else _mamba_mixer
            proj = mixer(h, layer_params, cfg)
        else:
            attn = _attention(q, k, v, cfg, mesh, window)
    with jax.named_scope("sec_attn_proj"):
        if not linear:
            attn = attn.reshape(b, t, layer_params["wo"].shape[-2])
            proj = attn @ layer_params["wo"].astype(x.dtype)
        if tp_axis is not None:
            proj = leave(proj)
        if post:
            proj = _rms_norm(proj, gamma_attn, cfg.norm_eps)
        # Selective-remat tag: saving the post-attention residual stream lets
        # the MLP recompute chain start HERE instead of replaying qkv →
        # attention → wo to rebuild it ("save:resid_mid"; the *_mid tiers keep
        # the attention output one product upstream instead, _REMAT_SAVE_SETS).
        x = checkpoint_name(x + proj, "resid_mid")

    with jax.named_scope("sec_mlp"):
        h = x if post else anchor_tokens(_rms_norm(x, gamma_mlp, cfg.norm_eps))
    if cfg.n_experts and not dense:
        moe_out, aux = _moe_mlp(h, layer_params, cfg, mesh,
                                bound_ep=bound_ep,
                                gate_logits=gate_logits)
        with jax.named_scope("sec_mlp"):
            if cfg.n_shared_experts:
                # the shared expert: every token, whole on every chip of a share
                dt = x.dtype
                moe_out = moe_out + (
                    jax.nn.silu(h @ layer_params["ws_gate"].astype(dt))
                    * (h @ layer_params["ws_up"].astype(dt))
                ) @ layer_params["ws_down"].astype(dt)
            return x + moe_out, aux
    with jax.named_scope("sec_mlp"):
        if tp_axis is not None:
            h = enter(h)
        # PRE-activation tags: the silu backward needs the pre-activation
        # value (silu'(z) is a function of z, not of silu(z)), so saving z
        # rather than silu(z) is what actually retires the gate/up matmul
        # recompute — the elementwise silu/mul replay from z is free.
        z_gate = checkpoint_name(h @ layer_params["w_gate"].astype(x.dtype), "mlp_gate")
        up = checkpoint_name(h @ layer_params["w_up"].astype(x.dtype), "mlp_up")
        down = (jax.nn.silu(z_gate) * up) @ layer_params["w_down"].astype(x.dtype)
        if tp_axis is not None:
            down = leave(down)
        if post:
            down = _rms_norm(down, gamma_mlp, cfg.norm_eps)
        return x + down, None


# one step's routing counters (_moe_single_gmm's stats), summed over layers as ``moe_<name>``
MOE_COUNTERS = ("routed_here", "rows_computed", "held_load_max", "held_load_mean",
                "rows_walked", "rows_bound")


def _router_logits(h, layer_params, cfg: TransformerConfig):
    """[b, t, d] -> [b·t, E] router scores of every expert."""
    flat = h.reshape(-1, h.shape[-1])
    if cfg.router_f32:
        return jnp.dot(flat.astype(jnp.float32), layer_params["w_router"],
                       precision=jax.lax.Precision.HIGHEST)
    return flat @ layer_params["w_router"].astype(h.dtype)


def _router_kwargs(layer_params, cfg: TransformerConfig) -> Dict[str, Any]:
    """moe_apply's router arguments beyond the softmax default ({} for it,
    so today's configs make today's call): the sigmoid score, its scale,
    and the layer's balancing bias — state the caller laid beside the
    layer's weights as ``router_bias`` (zeros when it handed none)."""
    if cfg.router_score == "softmax":
        return {}
    out = {"score": cfg.router_score, "scale": cfg.router_scale}
    if cfg.router_bias and "router_bias" in layer_params:
        out["bias"] = layer_params["router_bias"]
    return out


def _moe_mlp(h, layer_params, cfg: TransformerConfig, mesh,
             bound_ep: Optional[str] = None, gate_logits=None):
    """Top-k expert MLP (k = cfg.moe_top_k: 1 Switch / 2 Mixtral-style):
    router -> all-to-all dispatch over the ep axis (parallel.moe) ->
    per-expert SwiGLU -> gate-weighted combine.

    ``bound_ep`` (r4, ep-inside-pipeline): the caller already runs
    inside a shard_map that maps the ep axis (pipeline_apply binds every
    mesh axis), so moe_apply's own shard_map would nest — instead the
    per-device body (parallel.moe._moe_local) runs directly against the
    bound axis name: h is this shard's token slice, layer_params carry
    this shard's E/ep experts.

    Returns (out, aux) — aux carries the router losses (UNWEIGHTED; the
    loss head applies cfg.moe_aux_weight / cfg.moe_zloss_weight) plus
    observability stats: {"lb_loss", "z_loss", "expert_load" [E],
    "drop_frac"}."""
    from tf_operator_tpu.parallel.moe import (
        _moe_local,
        expert_activation,
        expert_capacity,
        moe_apply,
    )

    b, t, d = h.shape
    flat = h.reshape(b * t, d)
    if gate_logits is None:  # cfg.router_input == "mlp_norm"
        with jax.named_scope("sec_router"):
            gate_logits = _router_logits(h, layer_params, cfg)
    act = expert_activation(cfg.expert_act)

    def expert_fn(wp, toks):
        gate = act(toks @ wp["w_gate"].astype(toks.dtype))
        up = toks @ wp["w_up"].astype(toks.dtype)
        return (gate * up) @ wp["w_down"].astype(toks.dtype)

    expert_params = {
        "w_gate": layer_params["w_gate"],
        "w_up": layer_params["w_up"],
        "w_down": layer_params["w_down"],
    }
    if bound_ep is not None:
        # same capacity rule as moe_apply's sharded branch: flat is
        # already the per-shard token slice. dispatch follows
        # cfg.moe_dispatch with moe_apply's ladder semantics: gmm runs
        # padding-free in-stage (r6); einsum degrades to sort (the inbox
        # layout is identical, sort is the cheap form).
        from tf_operator_tpu.ops.grouped_matmul import gmm_block_rows

        local_impl = "gmm" if cfg.moe_dispatch == "gmm" else "sort"
        capacity = expert_capacity(
            cfg.capacity_factor, cfg.moe_top_k, flat.shape[0], cfg.n_experts
        )
        out, stats = _moe_local(
            flat, gate_logits, expert_params, expert_fn,
            axis_name=bound_ep, capacity=capacity, dropped="zero",
            k_top=cfg.moe_top_k, stat_axes=(bound_ep,),
            dispatch_impl=local_impl,
            block_rows=gmm_block_rows(),
            expert_act=cfg.expert_act,
        )
    else:
        out, stats = moe_apply(
            flat,
            gate_logits,
            expert_params,
            expert_fn,
            mesh,
            axis_name=AXIS_EXPERT,
            capacity_factor=cfg.capacity_factor,
            # the result feeds a residual add: a capacity-dropped token's
            # MLP must contribute 0, not its own input again
            dropped="zero",
            k_top=cfg.moe_top_k,
            return_stats=True,
            dispatch_impl=cfg.moe_dispatch,
            expert_act=cfg.expert_act,
            expert_first=cfg.expert_first,
            **_router_kwargs(layer_params, cfg),
        )
    # Switch load-balance loss: E * Σ_e f_e·P_e. f_e (expert_load) comes
    # out of the discrete top-k assignment, so it carries no gradient and
    # acts as a per-expert coefficient on the differentiable mean gate
    # probability — overloaded experts get their router prob pushed down.
    with jax.named_scope("sec_router"):
        lb_loss = cfg.n_experts * jnp.sum(
            stats["expert_load"] * stats["mean_gate"]
        )
        # ST-MoE router z-loss: keeps router logits near the softmax's
        # well-conditioned range.
        z = jax.scipy.special.logsumexp(gate_logits.astype(jnp.float32), axis=-1)
        z_loss = jnp.mean(jnp.square(z))
    aux = {
        "lb_loss": lb_loss,
        "z_loss": z_loss,
        "expert_load": stats["expert_load"],
        "drop_frac": stats["drop_frac"],
    }
    aux.update({k: stats[k] for k in MOE_COUNTERS if k in stats})
    if cfg.router_bias:
        aux["expert_count"] = stats["expert_count"]  # [E]: the bias update's input
    out = out.reshape(b, t, d)
    if bound_ep is None and mesh is not None and getattr(
        mesh, "devices", None
    ) is not None and AXIS_EXPERT in getattr(mesh, "axis_names", ()):
        # Re-anchor the layer output to the model's canonical activation
        # layout (batch over the data axes, ep REPLICATED). moe_apply's
        # shard_map constrains its flat tokens to P((dp, fsdp, ep)) —
        # correct inside the ep exchange, but without this anchor that
        # 8-way token sharding propagates OUT into the layer-scan carry
        # while the rest of the loop body (attention, residual adds)
        # settles on the (dp, fsdp)-only layout, and GSPMD reconciles
        # the conflicting while-carry specs with an "involuntary full
        # rematerialization" (replicate + re-slice of the carry AND the
        # downstream fused-CE block walk) on every layer iteration of
        # the ep×fsdp×dp flagship pass. Same anchoring rule as the
        # pipeline's microbatch split (parallel/pipeline.py).
        from jax.sharding import NamedSharding, PartitionSpec as P

        data_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
        if data_axes:
            out = jax.lax.with_sharding_constraint(
                out, NamedSharding(mesh, P(data_axes, None, None))
            )
    return out, aux


# Selective-remat policy ladder: named-activation sets
# between the two extremes full remat (save layer inputs only, fits, but
# replays qkv+attn+wo+gate+up in the backward) and "dots" (save every
# matmul output, OOMs at north-star shapes). Ordered by per-layer HBM cost
# at gqa-2048 b=6 t=2048 (bf16): flash_q 50.3 MB + flash_k/v 12.6 each;
# flash_o 50.3 (b·t·h·dv) + flash_lse 0.8 (b·t·h f32); resid_mid 50.3
# (b·t·d); mlp_up/mlp_gate 201 each. The recompute each name retires (in
# btd² matmul units of the 23 the full-remat backward replays — the down
# projection is never replayed, its output is dead in the backward):
# qkv 3, the attention forward ~2 (flash_o + flash_lse; on the chip the
# slowest 2: the flash kernels run at a quarter to a third of their
# roofline), wo 2 (resid_mid), up 8, gate 8.
#
# r5 measured the attention replay as "the structural floor of every
# tier"; what hid the flash custom-vjp's (o, lse) from name policies was
# its ``optimize_remat`` registration, not the boundary (ops/
# flash_attention.py FLASH_SAVE_NAMES). Since PR 33 both are nameable and
# every ``*_mid`` tier keeps THEM at the point where it kept ``resid_mid``:
# the recompute chain is still cut at the attention block — the backward
# replays norm → qkv (+ rotary) for the kernels' operands and the one
# ``o @ wo`` product for the residual stream behind it, and no flash_fwd.
# In place of, not beside: where h·dv = d the two weigh what resid_mid
# weighed, and a step that sat at its memory limit (mistral-7b 2 × 4096 on
# one v5e) answered all three names with the compiler's OWN
# rematerialisation of an MLP matmul — 15.7 ms a step for the 10.3 the
# kernel replay cost (PERF.md §6, PR 33). A job with HBM to spare states
# the larger set through the syntax that exists,
# ``remat="save:resid_mid,flash_o,flash_lse"`` (no replayed kernel AND no
# replayed wo), and ``"save:resid_mid"`` is the set these aliases had.
_MID = ("flash_o", "flash_lse")
_REMAT_SAVE_SETS: Dict[str, tuple] = {
    "save_mid": _MID,  # the tier the train cells run (benchmarks/configs/*)
    "save_qkv": ("flash_q", "flash_k", "flash_v"),
    "save_qkv_mid": ("flash_q", "flash_k", "flash_v") + _MID,
    "save_qkv_mid_up": ("flash_q", "flash_k", "flash_v") + _MID + ("mlp_up",),
    "save_qkv_mid_mlp": (
        "flash_q", "flash_k", "flash_v") + _MID + ("mlp_up", "mlp_gate"),
    "save_mlp_mid": _MID + ("mlp_gate", "mlp_up"),
}


# Every checkpoint_name tag the model actually emits (the flash inputs and
# outputs from ops/flash_attention.FLASH_SAVE_NAMES + the layer-body tags
# above) — the validation domain for user "save:" policies.
KNOWN_SAVE_NAMES = frozenset(
    {"flash_q", "flash_k", "flash_v", "flash_o", "flash_lse",
     "resid_mid", "mlp_gate", "mlp_up"}
)


def remat_save_names(remat) -> Optional[tuple]:
    """The activation names a remat mode saves (None for non-name modes).
    Accepts the _REMAT_SAVE_SETS aliases or ``"save:name1,name2"``.
    Unknown names in a ``save:`` policy are rejected: a typo
    (save:resid_mld) would otherwise save NOTHING and silently degrade
    to full remat — the opposite of what the user asked for."""
    if isinstance(remat, str):
        if remat in _REMAT_SAVE_SETS:
            return _REMAT_SAVE_SETS[remat]
        if remat.startswith("save:"):
            names = tuple(n.strip() for n in remat[5:].split(",") if n.strip())
            unknown = sorted(set(names) - KNOWN_SAVE_NAMES)
            if unknown:
                raise ValueError(
                    f"remat policy {remat!r}: unknown activation name(s) "
                    f"{unknown} — no such checkpoint_name tag exists, so "
                    "they would save nothing (silent full remat); known "
                    f"names: {sorted(KNOWN_SAVE_NAMES)}"
                )
            return names
    return None


def checkpoint_name(x, name: str):
    """jax.ad_checkpoint.checkpoint_name on every array leaf — identity
    outside remat; under a save_only_these_names policy the tagged value
    is stored instead of recomputed."""
    from jax.ad_checkpoint import checkpoint_name as cn

    return jax.tree_util.tree_map(lambda a: cn(a, name), x)


def _remat_wrap(layer_fn, cfg: TransformerConfig):
    if cfg.remat in (True, "full"):
        return jax.checkpoint(layer_fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            layer_fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    names = remat_save_names(cfg.remat)
    if names is not None:
        return jax.checkpoint(
            layer_fn, policy=jax.checkpoint_policies.save_only_these_names(*names)
        )
    if cfg.remat not in (False, None, "none"):
        raise ValueError(f"unknown remat mode {cfg.remat!r}")
    return layer_fn


def _use_pipeline(cfg: TransformerConfig, mesh) -> bool:
    return bool(
        cfg.pp_microbatches
        and mesh is not None
        and AXIS_PIPELINE in getattr(mesh, "axis_names", ())
        and mesh.shape[AXIS_PIPELINE] > 1
    )


def _pp_param_specs(cfg: TransformerConfig, tp_axis: Optional[str],
                    bound_ep: Optional[str]):
    """PartitionSpecs for the stage-major [S, per_stage, ...] layer params,
    from the leaves' logical axes: the stage dim over pp; with tp, the
    Megatron split — the dimension that carries heads / kv_heads / mlp over
    tp (wq/wk/wv/w_gate/w_up column-parallel, wo/w_down row-parallel), norms
    replicated; under ep-in-stage the experts' own dimension over ep, so each
    device holds its stage's layers x its E/ep experts — and the router
    whole: every device scores every expert."""
    from jax.sharding import PartitionSpec as P

    over = {"heads": tp_axis, "kv_heads": tp_axis, "mlp": tp_axis, "expert": bound_ep}
    return {
        leaf.name: P(AXIS_PIPELINE, None, *(
            None if leaf.name == "w_router" else over.get(a) for a in leaf.axes[1:]))
        for leaf in layer_leaves(cfg, cfg.n_layers, False)
    }


def transformer_hidden_pp(params, tokens, cfg: TransformerConfig, mesh):
    """Pipeline-parallel layer stack: n_layers/pp contiguous layers per
    stage through parallel.pipeline.pipeline_apply (fill-drain pipeline —
    "1f1b" explicit-backward schedule by default, cfg.pp_schedule —
    activations over ppermute). The per-stage body is itself a lax.scan
    over the stage's layers — the same stacked-params execution the
    single-device path uses, so the oracle comparison is exact math.

    Composes with dp (each dp group pipelines its batch slice) and, r3,
    with tp-WITHIN-STAGE: with a tp axis in the mesh, stage weights shard
    Megatron-style (_pp_param_specs) and _layer psums its row-parallel
    matmuls over tp.

    MoE + pipeline: experts REPLICATE within each stage by default (the
    moe_apply no-ep routing path — identical math to the ep-sharded
    dispatch); with an ep axis in the mesh, experts SHARD over ep inside
    each stage: pipeline_apply's
    one shard_map binds every mesh axis, so the stage body runs
    parallel.moe._moe_local directly against the bound "ep" name (no
    nesting) — tokens shard over (dp, fsdp, ep) as additional pipeline
    data axes, expert weights shard over (pp on the stage dim, ep on the
    expert dim), and the all-to-all dispatch runs per (stage,
    microbatch). The router aux losses ride the pipeline's aux channel
    (pipeline_apply aux_size=2: summed lb/z per (stage-layer,
    microbatch), normalized back to means here) so MoE trains at quality
    under pp — with the caveat that load-balance fractions are computed
    per MICROBATCH rather than per batch. Per-layer router telemetry
    (expert_load/drop_frac) is not carried through the pipeline;
    lm_loss_and_metrics reports the scalar losses only for pp+MoE.
    MoE + tp-within-stage is rejected (the expert MLP has no tp
    split)."""
    from tf_operator_tpu.parallel.pipeline import pipeline_apply

    if len(cfg.pattern) > 1 or cfg.n_held < cfg.n_experts:
        raise NotImplementedError(
            "the pipelined stack runs one layer kind and whole expert "
            "layers; a layer pattern or a share of the experts is not "
            "stage-partitioned yet"
        )
    if cfg.n_experts and "tp" in mesh.axis_names and mesh.shape["tp"] > 1:
        raise NotImplementedError(
            "MoE + tp-within-stage is not supported (the expert MLP has "
            "no tensor-parallel split); use pp x ep x dp for MoE pipelines"
        )
    ep_in_stage = bool(
        cfg.n_experts
        and AXIS_EXPERT in mesh.axis_names
        and mesh.shape[AXIS_EXPERT] > 1
    )
    if ep_in_stage and cfg.n_experts % mesh.shape[AXIS_EXPERT]:
        raise ValueError(
            f"{cfg.n_experts} experts not divisible by "
            f"{AXIS_EXPERT}={mesh.shape[AXIS_EXPERT]}"
        )
    bound_ep = AXIS_EXPERT if ep_in_stage else None
    n_stages = mesh.shape[AXIS_PIPELINE]
    n_virtual = n_stages * cfg.pp_chunks
    if cfg.n_layers % n_virtual:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pp*pp_chunks="
            f"{n_virtual}"
        )
    tp_axis = None
    if "tp" in mesh.axis_names and mesh.shape["tp"] > 1:
        tp = mesh.shape["tp"]
        for nm, val in (("n_heads", cfg.n_heads), ("n_kv_heads", cfg.n_kv_heads),
                        ("d_ff", cfg.d_ff)):
            if val % tp:
                raise ValueError(f"{nm}={val} not divisible by tp={tp}")
        tp_axis = "tp"
    with jax.named_scope("sec_embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
    layer_fn = _remat_wrap(
        partial(_layer, cfg=cfg, mesh=None, tp_axis=tp_axis,
                tp_manual_vjp=(cfg.pp_schedule == "1f1b"),
                bound_ep=bound_ep),
        cfg,
    )
    moe = bool(cfg.n_experts)

    if moe:
        def stage_fn(stage_layers, xb):
            def body(carry, lp):
                h, acc = carry
                out, aux = layer_fn(h, lp)
                acc = acc + jnp.stack(
                    [aux["lb_loss"], aux["z_loss"]]
                ).astype(jnp.float32)
                return (out, acc), None

            (out, acc), _ = jax.lax.scan(
                body, (xb, jnp.zeros((2,), jnp.float32)), stage_layers
            )
            return out, acc
    else:
        def stage_fn(stage_layers, xb):
            def body(h, lp):
                out, _ = layer_fn(h, lp)
                return out, None

            out, _ = jax.lax.scan(body, xb, stage_layers)
            return out

    per_stage = cfg.n_layers // n_virtual
    stage_params = jax.tree_util.tree_map(
        lambda a: a.reshape((n_virtual, per_stage) + a.shape[1:]),
        params["layers"],
    )
    res = pipeline_apply(
        stage_params, x, stage_fn, mesh, cfg.pp_microbatches, AXIS_PIPELINE,
        schedule=cfg.pp_schedule,
        # with ep-in-stage the ep axis is a pipeline DATA axis too: each
        # (dp, ep) coordinate pipelines its own token slice, and the MoE
        # layers all-to-all those slices to the expert owners over ep
        batch_axes=(("dp", "fsdp", AXIS_EXPERT) if ep_in_stage
                    else ("dp", "fsdp")),
        param_specs=_pp_param_specs(cfg, tp_axis, bound_ep),
        aux_size=2 if moe else 0,
        n_chunks=cfg.pp_chunks,
    )
    if moe:
        h, aux_sums = res
        # sums over (layers x microbatches) -> the means the loss head
        # expects (matching the non-pp per-layer-mean semantics up to
        # microbatched load-balance fractions)
        denom = cfg.n_layers * cfg.pp_microbatches
        aux = {
            "lb_loss": aux_sums[0] / denom,
            "z_loss": aux_sums[1] / denom,
            "expert_load": None,  # per-layer telemetry not carried via pp
            "drop_frac": None,
        }
        with jax.named_scope("sec_head_ce"):
            return _rms_norm(h, params["final_norm"], cfg.norm_eps), aux
    with jax.named_scope("sec_head_ce"):
        return _rms_norm(res, params["final_norm"], cfg.norm_eps), None


def transformer_hidden(params, tokens, cfg: TransformerConfig, mesh=None,
                       with_aux: bool = False, router_bias=None):
    """tokens: [b, t] int32 -> final-norm hidden states [b, t, d] (cfg.dtype).

    ``router_bias`` [expert layers, E] float32: the balancing bias of a
    bias-balanced router, one row a scanned layer (state, not a parameter;
    None: zeros). ``aux`` then also carries ``expert_count`` [L, E].

    ``with_aux`` also returns the MoE router aux dict (None for dense):
    {"lb_loss", "z_loss" — mean over layers, unweighted;
    "expert_load" [L, E], "drop_frac" [L] — per layer, for telemetry}.

    With cfg.pp_microbatches set and a pp axis in the mesh, the layer
    stack runs as a GPipe pipeline (transformer_hidden_pp)."""
    if _use_pipeline(cfg, mesh):
        h, aux = transformer_hidden_pp(params, tokens, cfg, mesh)
        return (h, aux) if with_aux else h
    # Pin the layer-scan carry to the canonical activation layout (batch
    # over the data axes) for MoE configs. A while-loop carry must keep
    # ONE sharding across init/body-input/body-output; the MoE body
    # contains moe_apply's shard_map, whose in/out specs constrain the
    # flat token slab to P((dp, fsdp, ep)) — that 8-way sharding
    # propagates through the entry/exit reshapes onto the carry, while
    # the embedding gather hands the INIT a d-over-fsdp layout (the ZeRO
    # table sharding) and the rest of the body settles on (dp, fsdp)
    # batch sharding. GSPMD reconciles the disagreeing carry specs with
    # an "involuntary full rematerialization" (replicate + re-slice) of
    # the carry every iteration — the moe-fsdp warning pair the r5
    # verdict pinned. Two anchors fix the disagreement at its sources:
    # the embedding TABLE is read through a replicated constraint (the
    # all-gather ZeRO pays at first use anyway, made explicit so the
    # gather's output is batch-sharded like the loop), and the body
    # output re-anchors after the MoE layer (see _moe_mlp's matching
    # anchor). Dense configs are unaffected.
    carry_anchor = None
    if mesh is not None and getattr(mesh, "devices", None) is not None:
        data_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
        # scoped to MoE-on-ep-mesh: only moe_apply's shard_map injects
        # the competing token spec; elsewhere propagation is already
        # consistent and anchors would just constrain it for nothing
        if (cfg.n_experts and data_axes
                and AXIS_EXPERT in mesh.axis_names):
            from jax.sharding import NamedSharding, PartitionSpec as P

            carry_anchor = NamedSharding(mesh, P(data_axes, None, None))
    with jax.named_scope("sec_embed"):
        et = params["embed"].astype(cfg.dtype)
        if carry_anchor is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            et = jax.lax.with_sharding_constraint(
                et, NamedSharding(mesh, P(None, None))
            )
        x = et[tokens]
    if carry_anchor is not None:
        # The token-embedding-gradient scatter-add (this gather's
        # transpose) accumulates into the table's layout; handing it the
        # batch-sharded backward cotangent makes GSPMD replicate +
        # re-slice it INVOLUNTARILY (the last remat warning of the
        # moe-fsdp pass). The movement is unavoidable — the cotangent
        # genuinely changes layout axes — so do the same replicate
        # explicitly in the backward only: identity forward, cotangent
        # constrained replicated. Same bytes on the wire, zero warnings,
        # and the forward pays nothing.
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(mesh, P(*(None,) * x.ndim))

        @jax.custom_vjp
        def _bwd_replicate(a):
            return a

        def _br_fwd(a):
            return a, None

        def _br_bwd(_, g):
            return (jax.lax.with_sharding_constraint(g, rep),)

        _bwd_replicate.defvjp(_br_fwd, _br_bwd)
        with jax.named_scope("sec_embed"):
            x = _bwd_replicate(x)

    # The leading dense layers: another SHAPE than the scanned stack's, so
    # a section of their own in front of it, each under its own remat.
    for j in range(cfg.n_dense_lead):
        lead_fn = _remat_wrap(
            partial(_layer, cfg=cfg, mesh=mesh, kind=cfg.pattern[0], dense=True),
            cfg)
        with jax.named_scope("sec_stack"):
            x, _ = lead_fn(x, jax.tree_util.tree_map(lambda a: a[j], params["lead"]))

    # One scan step is one PERIOD of the layer pattern, its layers
    # unrolled, each with its own static (window, rotary) and its own
    # remat boundary; a pattern of one entry is the plain per-layer scan.
    pattern = cfg.pattern
    stack = params["layers"]
    if cfg.router_bias and router_bias is not None:
        # the bias rides the scan beside the layer's weights (it only picks
        # the top-k's indices, so no gradient reaches it)
        stack = dict(stack, router_bias=router_bias)
    layer_fns = [
        _remat_wrap(partial(_layer, cfg=cfg, mesh=mesh, kind=kind), cfg)
        for kind in pattern
    ]

    def one_layer(layer_fn, x, layer_params):
        if carry_anchor is not None:
            # input-side: without this, the moe shard_map's 8-way token
            # spec back-propagates through rms_norm/reshape onto the
            # while-body PARAMETER and outvotes the output-side anchor
            x = jax.lax.with_sharding_constraint(x, carry_anchor)
        new_x, aux = layer_fn(x, layer_params)  # (new_x, per-layer aux or None)
        if carry_anchor is not None:
            new_x = jax.lax.with_sharding_constraint(new_x, carry_anchor)
        return new_x, aux

    if len(pattern) == 1:
        # kept apart on purpose: the period body at a period of one lowers
        # to ANOTHER program for the described v5e (4,086 HLO lines and 45
        # custom calls against 3,919 and 39 at the dense 7B step), and the
        # dense cells are held to the program they had
        with jax.named_scope("sec_stack"):
            x, aux_stack = jax.lax.scan(
                partial(one_layer, layer_fns[0]), x, stack)
    else:
        P = len(pattern)
        stacks = stacked_by(cfg)

        def layer_of(period_params, j):
            """Layer j of a period: its leaves at j — a mixer's at the
            layer's place among the period's layers of its kind, where the
            mixers are stacked by kind."""
            if not cfg.recurrent_kind:
                return jax.tree_util.tree_map(lambda a: a[j], period_params)
            i = cfg.kind_index(j)
            mine = kind_of(pattern[j])
            return {name: a[i if stacks[name] == mine else j]
                    for name, a in period_params.items()
                    if stacks[name] in (None, mine)}

        def period_body(x, period_params):
            auxes = []
            for j, layer_fn in enumerate(layer_fns):
                x, aux = one_layer(layer_fn, x, layer_of(period_params, j))
                auxes.append(aux)
            if auxes[0] is None:
                return x, None
            return x, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *auxes)

        with jax.named_scope("sec_stack"):
            x, aux_stack = jax.lax.scan(
                period_body, x,
                jax.tree_util.tree_map(  # [periods, the period's layers (of the leaf's kind), ...]
                    lambda a: a.reshape(
                        (cfg.n_stack_layers // P, a.shape[0] * P // cfg.n_stack_layers)
                        + a.shape[1:]),
                    stack))
        if aux_stack is not None:  # [periods, P, ...] -> [L, ...]
            aux_stack = jax.tree_util.tree_map(
                lambda a: a.reshape((cfg.n_stack_layers,) + a.shape[2:]), aux_stack)
    if carry_anchor is not None:
        # exit anchor: pins the BACKWARD scan's carry init too — the
        # transpose of this constraint re-anchors the loss head's
        # incoming cotangent before it becomes the reverse while carry,
        # so the fused-CE block walk and the backward loop agree on the
        # batch layout instead of full-rematerializing per layer
        x = jax.lax.with_sharding_constraint(x, carry_anchor)
    with jax.named_scope("sec_head_ce"):
        h = _rms_norm(x, _anchored_gamma(params["final_norm"], cfg, mesh),
                      cfg.norm_eps)
    if not with_aux:
        return h
    if aux_stack is None:
        return h, None
    with jax.named_scope("sec_router"):
        aux = {
            "lb_loss": jnp.mean(aux_stack["lb_loss"]),
            "z_loss": jnp.mean(aux_stack["z_loss"]),
            "expert_load": aux_stack["expert_load"],  # [L, E]
            "drop_frac": aux_stack["drop_frac"],  # [L]
        }
        # routing counters (gmm dispatch): one scalar a step, summed over layers
        aux.update({k: jnp.sum(aux_stack[k]) for k in MOE_COUNTERS if k in aux_stack})
    if "expert_count" in aux_stack:
        aux["expert_count"] = aux_stack["expert_count"]  # [L, E]
    return h, aux


def mtp_hidden(params, tokens, h, cfg: TransformerConfig, mesh=None,
               router_bias=None):
    """The multi-token-prediction module (DeepSeek-V3's, depth 1): for the
    main model's final-normed states h [b, t, d] of tokens t_0..t_{T-1},
    x_i = [RMSNorm_e(Emb(t_{i+1})) | RMSNorm_h(h_i)] · W_eh, one whole layer
    of the stack's kind over x (its own router bias ``router_bias`` [1, E]),
    its own final norm. Returns (states [b, t, d] whose row i predicts
    t_{i+2}, the layer's aux or None).

    Every row of T positions goes through, the last two fed the row's own
    first tokens (a roll): under causal attention they reach no other
    position and the loss leaves them out, and T stays the length the
    flash kernels tile."""
    m = params["mtp"]
    dt = cfg.dtype
    with jax.named_scope("sec_embed"):  # the module's input: lookup, two norms, W_eh
        e_next = params["embed"].astype(dt)[jnp.roll(tokens, -1, axis=1)]
        x = jnp.concatenate(
            [_rms_norm(e_next, m["norm_e"], cfg.norm_eps),
             _rms_norm(h, m["norm_h"], cfg.norm_eps)], axis=-1) @ m["w_eh"].astype(dt)
    with jax.named_scope("sec_stack"):
        lp = jax.tree_util.tree_map(lambda a: a[0], m["layer"])
        if cfg.router_bias and router_bias is not None:
            lp = dict(lp, router_bias=router_bias[0])
        layer_fn = _remat_wrap(
            partial(_layer, cfg=cfg, mesh=mesh, kind=cfg.pattern[0],
                    dense=not cfg.n_experts), cfg)
        x, aux = layer_fn(x, lp)
    with jax.named_scope("sec_head_ce"):
        return _rms_norm(x, m["final_norm"], cfg.norm_eps), aux


def transformer_forward(params, tokens, cfg: TransformerConfig, mesh=None):
    """tokens: [b, t] int32 -> logits [b, t, vocab] (f32)."""
    x = transformer_hidden(params, tokens, cfg, mesh)
    # the output head: embed^T when tied
    return (x @ _head(params, cfg).astype(cfg.dtype).T).astype(jnp.float32)


def _head(params, cfg: TransformerConfig):
    """The [vocab, d] output head: the embedding when tied."""
    return params["embed"] if cfg.tied_head else params["head"]


MASK_TOKEN = 0


def lm_loss_and_metrics(params, tokens, cfg: TransformerConfig, mesh=None, key=None,
                        mask_rate=0.15, router_bias=None):
    """Causal: next-token cross entropy. Bidirectional (BERT-class): masked
    language modeling — ``mask_rate`` of positions are replaced with
    MASK_TOKEN and only those positions contribute to the loss (training on
    unmasked inputs would be degenerate identity reconstruction).

    Returns (total_loss, metrics). For MoE configs the total includes the
    weighted router losses and metrics carries the router telemetry:
    ce_loss, moe_lb_loss, moe_z_loss (unweighted), moe_expert_entropy
    (mean over layers, nats — uniform routing = ln(E)), moe_drop_frac.

    With an MTP module the total is ``ce + mtp_weight · ce_mtp`` and
    metrics carries both (``loss_main``, ``loss_mtp``); the routing
    counters then sum over the module's expert layer too. With a
    bias-balanced router, ``router_bias`` ({"layers": [L, E], "mtp":
    [1, E]}, None: zeros) steers this step's choices and metrics carries
    the NEXT step's under the same key — b + rate · sign(mean load −
    load_e), loads counted over this call's tokens and all E outputs — with
    ``moe_bias_abs_max`` and ``moe_all_load_max_over_mean``."""
    if cfg.router_bias and router_bias is None:
        router_bias = zero_router_bias(cfg)

    def _hidden(inp):
        return transformer_hidden(
            params, inp, cfg, mesh, with_aux=True,
            router_bias=router_bias["layers"] if cfg.router_bias else None)

    from tf_operator_tpu.ops.fused_cross_entropy import (
        data_parallel_axes,
        fused_cross_entropy,
    )

    head = _head(params, cfg)

    def _ce_operands(h, embed):
        # MoE on a multi-axis mesh (r6): pin the fused-CE block walk to
        # the batch-sharded layout with the EMBED all-gathered. Left to
        # propagation, the ZeRO-sharded embed (d over fsdp, a TRANSPOSED
        # device order on the dp×fsdp×ep mesh) pulls the CE loop's xs/dx
        # carries toward d-over-fsdp while the anchored hidden states
        # arrive batch-sharded — and converting between differently
        # ORDERED tile assignments is exactly what GSPMD can only do by
        # involuntary full rematerialization, once per block per layer.
        # The all-gathered embed transient is vocab·d·dtype — at mixtral
        # shapes ~256 MB bf16, far below the [b·t, vocab] psum the
        # d-sharded assignment pays instead. That psum is what propagation
        # picks on a mesh of data axes alone too (two all-reduces of the
        # f32 logits tile a block, 9 % of the fsdp=4 step on the chip, PR
        # 31), but this anchor is NOT the cure there: it shards the block
        # walk's SCAN dimension and keeps an f32 all-reduce of the head's
        # gradient in every block. Such a mesh takes fused_cross_entropy's
        # own partition (its ``mesh``); the anchor stays scoped to ep.
        if data_parallel_axes(mesh) or not (
                cfg.n_experts and mesh is not None
                and getattr(mesh, "devices", None) is not None
                and AXIS_EXPERT in getattr(mesh, "axis_names", ())):
            return h, embed
        data_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
        if not data_axes:
            return h, embed
        from jax.sharding import NamedSharding, PartitionSpec as P

        flat_h = jax.lax.with_sharding_constraint(
            h.reshape(-1, h.shape[-1]), NamedSharding(mesh, P(data_axes, None)))
        embed = jax.lax.with_sharding_constraint(
            embed, NamedSharding(mesh, P(None, None)))
        return flat_h, embed

    def _ce(h, targets, weights=None):
        """Mean cross-entropy of h [b, t, d] through the head against
        targets [b, t] (weights [b, t]: a weighted mean)."""
        if cfg.fused_xent:
            return fused_cross_entropy(
                *_ce_operands(h, head), targets, weights, mesh=mesh)
        logits = (h @ head.astype(cfg.dtype).T).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        if weights is None:
            return -jnp.mean(ll)
        return -jnp.sum(ll * weights) / jnp.maximum(jnp.sum(weights), 1)

    if cfg.causal:
        h_all, aux = _hidden(tokens)
        with jax.named_scope("sec_head_ce"):
            ce = _ce(h_all[:, :-1], tokens[:, 1:])
    else:
        if key is None:
            key = jax.random.PRNGKey(0)
        mask = jax.random.bernoulli(key, mask_rate, tokens.shape)
        inputs = jnp.where(mask, MASK_TOKEN, tokens)
        h_all, aux = _hidden(inputs)
        with jax.named_scope("sec_head_ce"):
            ce = _ce(h_all, tokens, mask)

    metrics = {"ce_loss": ce}
    total = ce
    if aux is not None:
        if cfg.moe_aux_weight or cfg.moe_zloss_weight:
            total = (
                ce
                + cfg.moe_aux_weight * aux["lb_loss"]
                + cfg.moe_zloss_weight * aux["z_loss"]
            )
        metrics.update(moe_lb_loss=aux["lb_loss"], moe_z_loss=aux["z_loss"])
        if aux.get("expert_load") is not None:
            # per-layer router telemetry (absent under pipeline parallelism
            # — only the scalar losses ride the pp aux channel)
            load = aux["expert_load"]  # [L, E]
            p = load / jnp.maximum(jnp.sum(load, axis=-1, keepdims=True), 1e-9)
            entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-9)), axis=-1)  # [L]
            metrics.update(
                moe_expert_entropy=jnp.mean(entropy),
                moe_drop_frac=jnp.mean(aux["drop_frac"]),
            )
        metrics.update({f"moe_{k}": aux[k] for k in MOE_COUNTERS if k in aux})

    counts = None if aux is None else aux.get("expert_count")  # [L, E] int32
    if cfg.mtp_depth:
        h_mtp, aux_mtp = mtp_hidden(
            params, tokens, h_all, cfg, mesh,
            router_bias["mtp"] if cfg.router_bias else None)
        t = tokens.shape[1]
        with jax.named_scope("sec_head_ce"):
            live = jnp.broadcast_to(jnp.arange(t) < t - 2, tokens.shape)
            ce_mtp = _ce(h_mtp, jnp.roll(tokens, -2, axis=1), live)
        total = total + cfg.mtp_weight * ce_mtp
        metrics.update(loss_main=ce, loss_mtp=ce_mtp)
        if aux_mtp is not None:
            for k in MOE_COUNTERS:
                if k in aux_mtp and f"moe_{k}" in metrics:
                    metrics[f"moe_{k}"] = metrics[f"moe_{k}"] + aux_mtp[k]
            if counts is not None:
                counts = jnp.concatenate(
                    [counts, aux_mtp["expert_count"][None]], axis=0)
    if cfg.router_bias and counts is not None:
        with jax.named_scope("sec_router"):
            metrics.update(_next_router_bias(cfg, router_bias, counts))
    return total, metrics


def _next_router_bias(cfg: TransformerConfig, bias, counts) -> Dict[str, Any]:
    """The balancing bias after a step whose routers chose ``counts`` [expert
    layers (+ the module's), E] int32: b + rate * sign(mean load - load_e),
    compared in whole numbers (a layer's choices over E against E times an
    expert's own), and the two scalars that describe it."""
    per_layer = jnp.sum(counts, axis=-1, keepdims=True)
    step = cfg.router_bias_rate * jnp.sign(
        per_layer - counts * cfg.n_experts).astype(jnp.float32)
    n = cfg.n_stack_layers
    new_bias = {"layers": bias["layers"] + step[:n]}
    if cfg.mtp_depth:
        new_bias["mtp"] = bias["mtp"] + step[n:]
    load = counts.astype(jnp.float32)
    return dict(
        router_bias=new_bias,
        moe_bias_abs_max=jnp.max(jnp.stack(
            [jnp.max(jnp.abs(v)) for v in new_bias.values()])),
        moe_all_load_max_over_mean=jnp.sum(jnp.max(load, axis=-1))
        / jnp.sum(jnp.mean(load, axis=-1)),
    )


def lm_loss(params, tokens, cfg: TransformerConfig, mesh=None, key=None, mask_rate=0.15):
    """Scalar training loss (lm_loss_and_metrics without the telemetry);
    includes the weighted MoE router losses for MoE configs."""
    total, _ = lm_loss_and_metrics(params, tokens, cfg, mesh, key, mask_rate)
    return total


def zero_router_bias(cfg: TransformerConfig) -> Dict[str, Any]:
    """The balancing bias a bias-balanced router starts from: zeros, one
    row an expert layer of the stack and of the MTP module."""
    bias = {"layers": jnp.zeros((cfg.n_stack_layers, cfg.n_experts), jnp.float32)}
    if cfg.mtp_depth:
        bias["mtp"] = jnp.zeros((cfg.mtp_depth, cfg.n_experts), jnp.float32)
    return bias


def moe_counter_names(cfg: TransformerConfig, mesh=None) -> tuple:
    """The device scalars a step of this config returns beside its loss:
    the ``moe_*`` routing counters for gmm dispatch on the one-device
    expert path (no exchange over an ep axis, no pipeline), there also
    both partial losses of a model with an MTP module and the two numbers
    of a router bias; () otherwise."""
    sharded = mesh is not None and any(
        mesh.shape.get(a, 1) > 1 for a in (AXIS_EXPERT, AXIS_PIPELINE))
    if not cfg.n_experts or cfg.moe_dispatch != "gmm" or sharded:
        return ()
    names = tuple(f"moe_{k}" for k in MOE_COUNTERS)
    if cfg.mtp_depth:
        names += ("loss_main", "loss_mtp")
    if cfg.router_bias:
        names += ("moe_bias_abs_max", "moe_all_load_max_over_mean")
    return names


def lm_loss_with_counters(params, tokens, cfg: TransformerConfig, mesh=None,
                          extra=None):
    """(loss, new_extra) in the shape ``Trainer`` takes: the step's device
    scalars — one per name of moe_counter_names(cfg) — leave the step
    beside the loss as ``TrainState.extra``, with no host sync beyond the
    step's own. A bias-balanced router's bias is STATE on the same tree
    (``extra["router_bias"]``): read from ``extra``, returned updated.
    Start the state from ``zero_moe_counters(cfg)``."""
    bias = (extra or {}).get("router_bias") if cfg.router_bias else None
    total, metrics = lm_loss_and_metrics(params, tokens, cfg, mesh,
                                         router_bias=bias)
    out = {k: metrics[k].astype(jnp.float32)
           for k in moe_counter_names(cfg, mesh)}
    if cfg.router_bias:
        out["router_bias"] = metrics["router_bias"]
    return total, out


def zero_moe_counters(cfg: TransformerConfig, mesh=None) -> Dict[str, Any]:
    out = {k: jnp.zeros((), jnp.float32) for k in moe_counter_names(cfg, mesh)}
    if cfg.router_bias:
        out["router_bias"] = zero_router_bias(cfg)
    return out


def preset(name: str, **overrides) -> TransformerConfig:
    return replace(PRESETS[name], **overrides)


# Workload-dict keys accepted as TransformerConfig overrides: every field
# but the few a job does not set by its own name (``attn_impl`` is the key
# ``attn``). ONE set for every role reading the shared spec.workload
# (trainer lm.py, evaluator eval.py) — duplicated sets would let the roles
# build different configs from the same dict and fail at checkpoint restore.
CONFIG_OVERRIDE_FIELDS = frozenset(f.name for f in fields(TransformerConfig)) - {
    "rope_theta", "norm_eps", "dtype", "attn_impl", "pp_chunks"}


def preset_from_workload(workload: Dict[str, Any]) -> TransformerConfig:
    """TransformerConfig from a TPUJob workload dict: ``preset`` plus any
    CONFIG_OVERRIDE_FIELDS, with ``attn`` mapping to ``attn_impl``."""
    overrides = {k: workload[k] for k in CONFIG_OVERRIDE_FIELDS if k in workload}
    if "layer_pattern" in overrides:  # JSON lists -> the hashable tuple form
        overrides["layer_pattern"] = tuple(
            e if e in RECURRENT else (int(e[0]), bool(e[1]))
            for e in overrides["layer_pattern"])
    if workload.get("attn") in ("ring", "ulysses", "flash", "dense"):
        overrides["attn_impl"] = workload["attn"]
    return preset(workload.get("preset", "tiny"), **overrides)
