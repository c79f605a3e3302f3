"""Runner for the JoyAI-LLM-Flash share cell: ``runners/train.py``'s job with
what must differ and nothing else.

``TrainJob`` builds its trainer, follows its steps and decides ``correct``
in code this file may not edit and that has no hook for a second model, so
for the length of ``TrainJob.__init__`` the transformer's
``init_transformer`` gives the state its ``extra`` (the router's balancing
bias and the step's device scalars) and ``Trainer`` is built around the
loss that reads that state and returns the next (``TrainJob`` hands it a
loss that drops ``extra``); for the length of ``TrainJob.check`` the dense
reference's ``train_reference`` and the runner's ``compare`` are swapped
for this model's. Its own here: the size check against the config FILE,
the scalars it keeps (fetched once after a window), and the comparison's
numbers for the prediction module, the router and its bias.
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchmarks.runners import train
from benchmarks.runners.train_smallthinker import _host, diff_gaps, swapped

# what the program has to know of to build this configuration
NEEDS = ("attn_kind", "q_lora_rank", "kv_lora_rank", "qk_nope_dim",
         "qk_rope_dim", "v_head_dim", "n_dense_lead", "d_ff_dense",
         "router_score", "router_bias", "router_scale", "n_shared_experts",
         "mtp_depth", "mtp_weight", "tied_head", "experts_held")


def setup(ctx):
    return LatentShareTrainJob(ctx)


def model_sizes(ctx) -> Dict[str, Any]:
    """What the reference and the FLOP counts need, from the config FILE
    (never from the program's own config object)."""
    c = ctx.config
    a = c["assumed_values"]
    return dict(
        vocab=int(c["vocab_size"]), d_model=int(c["hidden_size"]),
        n_layers=int(c["num_hidden_layers"]),
        n_dense=int(c["first_k_dense_replace"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        q_rank=int(c["q_lora_rank"]), kv_rank=int(c["kv_lora_rank"]),
        nope=int(c["qk_nope_head_dim"]), rope=int(c["qk_rope_head_dim"]),
        v_dim=int(c["v_head_dim"]), d_ff=int(c["moe_intermediate_size"]),
        d_ff_dense=int(c["intermediate_size"]),
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        n_experts=int(c["moe_router_outputs"]),
        top_k=int(c["num_experts_per_tok"]),
        held=int(c["n_routed_experts"]), first=int(c["expert_first"]),
        n_shared=int(c["n_shared_experts"]),
        scale=float(c["routed_scaling_factor"]),
        bias_rate=float(a["router_bias_rate"]),
        mtp_weight=float(a["mtp_weight"]),
    )


def hold_to_file(tr, cfg, sizes, config) -> None:
    """The program's config against what the config FILE states."""
    built = dict(
        vocab=cfg.vocab, d_model=cfg.d_model, n_layers=cfg.n_layers,
        n_dense=cfg.n_dense_lead, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, q_rank=cfg.q_lora_rank,
        kv_rank=cfg.kv_lora_rank, nope=cfg.qk_nope_dim, rope=cfg.qk_rope_dim,
        v_dim=cfg.v_head_dim, d_ff=cfg.d_ff, d_ff_dense=cfg.d_ff_dense,
        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
        n_experts=cfg.n_experts, top_k=cfg.moe_top_k, held=cfg.n_held,
        first=cfg.expert_first, n_shared=cfg.n_shared_experts,
        scale=cfg.router_scale, bias_rate=cfg.router_bias_rate,
        mtp_weight=cfg.mtp_weight)
    for k, v in built.items():
        if v != sizes[k]:
            raise SystemExit(
                f"config {k}: the program builds {v}, the file states {sizes[k]}")
    stated = (config["scoring_func"], config["topk_method"], int(config["n_group"]),
              int(config["num_nextn_predict_layers"]),
              bool(config["tie_word_embeddings"]), config["hidden_act"])
    runs = (cfg.router_score, "noaux_tc" if cfg.router_bias else "greedy",
            cfg.router_groups, cfg.mtp_depth, cfg.tied_head, cfg.expert_act)
    if stated != runs:
        raise SystemExit(f"config: the file states {stated}, the program runs {runs}")
    if (cfg.attn_kind, cfg.router_f32, cfg.moe_aux_weight, cfg.moe_zloss_weight) != (
            "latent", True, 0.0, 0.0):
        raise SystemExit("config: latent attention and a float32 router with "
                         "no auxiliary loss are stated")


class LatentShareTrainJob(train.TrainJob):
    def __init__(self, ctx):
        from tf_operator_tpu.models import transformer as tr
        from tf_operator_tpu.train import trainer as trainer_mod

        missing = [k for k in NEEDS if k not in tr.CONFIG_OVERRIDE_FIELDS]
        if missing:
            raise SystemExit(
                "this program cannot build the configuration: its "
                f"transformer has no {missing} — no result")
        self.sizes = model_sizes(ctx)
        cfg = tr.preset_from_workload(ctx.config["workload"])
        hold_to_file(tr, cfg, self.sizes, ctx.config)
        # TrainJob holds the program to ctx.sizes by ITS names; d_ff there
        # is the program's, the expert width
        ctx.sizes = dict(ctx.sizes, **self.sizes)

        self.counters: List[Dict[str, Any]] = []
        self.grad1 = self.bias_followed = None
        init, Trainer = tr.init_transformer, trainer_mod.Trainer

        def stateful(mesh, loss_fn, **kw):
            del loss_fn  # TrainJob's drops the state; this one carries it
            return Trainer(
                mesh, loss_fn=lambda p, tokens, extra: tr.lm_loss_with_counters(
                    p, tokens, cfg, mesh=mesh, extra=extra), **kw)

        with swapped(tr, init_transformer=lambda key, c: (
                init(key, c), tr.zero_moe_counters(c))), \
                swapped(trainer_mod, Trainer=stateful):
            super().__init__(ctx)  # trainer, compile, loader, the followed steps
        first = _host(self.counters[:self.followed])
        self.program.update(
            routed_here=[c["moe_routed_here"] for c in first],
            losses_main=[c["loss_main"] for c in first],
            losses_mtp=[c["loss_mtp"] for c in first],
            grad1=self.grad1, bias=self.bias_followed)
        self.counters.clear()

    def _step(self) -> None:
        """``TrainJob._step``; the step's scalars stay on the device until
        the window is over. The next step is handed them as its donated
        ``extra`` but reads only the bias (the loss returns new scalars), so
        jit drops those arguments and their buffers stay alive; the bias IS
        consumed, so it is fetched where it is needed: after the last
        followed step of set-up."""
        super()._step()
        extra = self.state.extra
        self.counters.append({k: v for k, v in extra.items() if k != "router_bias"})
        if self.grad1 is None:
            # set-up's first step: the first gradient as the optimizer got it
            # (AdamW's first moment over 1 - beta1), whole, on the host
            import jax

            from benchmarks import reference

            mu = train._find_mu(self.state.opt_state)
            scale = 1.0 - self.ctx.config["optimizer"]["beta1"]
            self.grad1 = {
                k: v / scale for k, v in zip(
                    reference.leaf_names(mu),
                    jax.device_get(jax.tree_util.tree_leaves(mu)))}
        if self.bias_followed is None and len(self.counters) == self.followed:
            import jax
            import numpy as np

            b = jax.device_get(extra["router_bias"])
            self.bias_followed = np.concatenate([b["layers"], b["mtp"]])

    def window(self, seconds: float) -> Dict[str, Any]:
        samples = super().window(seconds)
        c = self.counters[:] = _host(self.counters)
        samples["counters"] = list(c)
        samples["model_sizes"] = self.sizes
        layers = self.sizes["n_layers"] - self.sizes["n_dense"] + 1
        last = c[-1]
        samples["notes"].update(
            routed_here_per_step=sum(x["moe_routed_here"] for x in c) / len(c),
            rows_per_held_expert_per_layer=sum(
                x["moe_held_load_mean"] for x in c) / len(c) / layers,
            loss_main_mtp_last=(last["loss_main"], last["loss_mtp"]),
            moe_bias_abs_max_last=last["moe_bias_abs_max"],
            moe_all_load_max_over_mean_first_last=(
                c[0]["moe_all_load_max_over_mean"],
                last["moe_all_load_max_over_mean"]),
        )
        return samples

    def traced_window(self) -> Dict[str, Any]:
        n0 = len(self.counters)
        out = super().traced_window()
        out["counters"] = self.counters[n0:] = _host(self.counters[n0:])
        return out

    def release(self) -> None:
        """``TrainJob.release``, and the step's executable with it: the
        reference needs the room (weights, one row's gradient and its
        temporaries fill what 10.9 GB of state left)."""
        import jax

        super().release()
        jax.clear_caches()

    def check(self, samples, control=None):
        """``TrainJob.check`` against this model's reference (``compare``)."""
        from benchmarks import reference, reference_joyai

        def noted(Check, program, ref, limits, prefix=""):
            gaps = diff_gaps(program["grad1"], ref["grad1"])
            self.ctx.say(f"note {prefix or 'program:'} grad1 difference by leaf: "
                         + ", ".join(f"{k}={v:.3e}" for k, v in sorted(gaps.items())))
            self.ctx.say(
                f"note {prefix or 'program:'} change_norms gap by leaf: " + ", ".join(
                    f"{k}={abs(program['change_norms'][k] - v) / v:.2e}"
                    for k, v in sorted(ref["change_norms"].items())))
            self.ctx.say(
                f"note {prefix or 'program:'} losses main {program['losses_main']} "
                f"(reference {ref['losses_main']}), module {program['losses_mtp']} "
                f"(reference {ref['losses_mtp']})")
            return compare(Check, program, ref, limits, prefix, gaps)

        with swapped(reference, train_reference=reference_joyai.train_reference), \
                swapped(train, compare=noted):
            return super().check(samples, control)


# the leaves whose gradient the router's choices decide, in the expert layers
# of the stack and of the module: a flipped near-tie moves a token's whole
# contribution from one expert to another (the dense lead's w_gate / w_up /
# w_down and every shared expert's ws_* lie in front of a router or beside it)
ROUTED = tuple(f"{where}/{leaf}" for where in ("layers", "mtp/layer")
               for leaf in ("w_router", "w_gate", "w_up", "w_down", "mlp_norm"))


def compare(Check, program, ref, limits, prefix="", gaps=None):
    """The followed steps against the reference's, as
    ``runners/train_smallthinker.compare`` (PERF.md §6, PR 26: the first
    gradient by each leaf's DIFFERENCE from the reference's, the worst
    leaf in front of a router and the worst behind one; the router's
    float32 product by the first step's count of choices routed to held
    experts), and what this model adds: the module's loss of step 1 on its
    own — the total would hide a missing or mis-shifted module behind the
    main loss — and the share of the balancing bias's entries (5 x 256)
    that differ from the reference's after the followed steps."""
    import numpy as np

    gaps = diff_gaps(program["grad1"], ref["grad1"]) if gaps is None else gaps
    out = [
        Check(f"{prefix}loss_step{i + 1}_abs_gap", abs(p - r),
              limits[f"loss_step{i + 1}_abs_gap"]["limit"])
        for i, (p, r) in enumerate(zip(program["losses"], ref["losses"]))
    ]
    out.append(Check(
        prefix + "loss_mtp_step1_abs_gap",
        abs(program["losses_mtp"][0] - ref["losses_mtp"][0]),
        limits["loss_mtp_step1_abs_gap"]["limit"]))
    for name, leaves in (
            ("grad1_diff_dense_leaf_gap", [k for k in gaps if k not in ROUTED]),
            ("grad1_diff_routed_leaf_gap", ROUTED)):
        out.append(Check(prefix + name, max(gaps[k] for k in leaves),
                         limits[name]["limit"]))
    out.append(Check(
        prefix + "param_change_norm_worst_leaf_gap",
        train.worst_leaf_gap(program["change_norms"], ref["change_norms"]),
        limits["param_change_norm_worst_leaf_gap"]["limit"]))
    n = ref["routed_here"][0]
    out.append(Check(
        prefix + "routed_step1_rel_gap", abs(program["routed_here"][0] - n) / n,
        limits["routed_step1_rel_gap"]["limit"]))
    out.append(Check(
        prefix + "bias_differs_share",
        float(np.mean(np.asarray(program["bias"]) != np.asarray(ref["bias"]))),
        limits["bias_differs_share"]["limit"]))
    return out
