"""Runner for the SmallThinker share cell: ``runners/train.py``'s job with
what must differ and nothing else.

``TrainJob`` builds its trainer, follows its steps and decides ``correct``
in code this file may not edit and that has no hook for a second model, so
for the length of ``TrainJob.__init__`` the transformer's ``lm_loss`` /
``init_transformer`` are swapped for the variants that carry the step's four
routing counters out beside the loss (``TrainState.extra``, as the LM
workload does for gmm-dispatched experts), and for the length of
``TrainJob.check`` the dense reference's ``train_reference`` and the
runner's ``compare`` are swapped for this model's. Its own here: the size
check (head width, layer pattern, router, share), the counters it keeps
(device scalars, fetched once after a window), and the comparison's two
router numbers.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Any, Dict, List

from benchmarks.runners import train


def setup(ctx):
    return ShareTrainJob(ctx)


@contextmanager
def swapped(obj, **names):
    """``obj``'s attributes set to ``names`` for the length of the block."""
    old = {k: getattr(obj, k) for k in names}
    for k, v in names.items():
        setattr(obj, k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(obj, k, v)


def layer_pattern(config, n_layers: int):
    """((window, rotary), ...) for the layers kept, from the published
    per-layer layouts, cut to one period if the layers repeat it."""
    kinds = [(int(config["sliding_window_size"]) if int(w) else 0, bool(r))
             for w, r in zip(config["sliding_window_layout"][:n_layers],
                             config["rope_layout"][:n_layers])]
    for p in range(1, n_layers + 1):
        if n_layers % p == 0 and kinds == kinds[:p] * (n_layers // p):
            return tuple(kinds[:p])


def model_sizes(ctx) -> Dict[str, Any]:
    """What the reference and the FLOP counts need, from the config FILE
    (never from the program's own config object)."""
    c = ctx.config
    w = c["workload"]
    return dict(
        ctx.sizes, head_dim=int(c["head_dim"]),
        n_experts=int(c["moe_router_outputs"]),
        top_k=int(c["moe_num_active_primary_experts"]),
        held=int(c["moe_num_primary_experts"]), first=int(c["expert_first"]),
        pattern=layer_pattern(c, int(c["num_hidden_layers"])),
        aux_weight=float(w["moe_aux_weight"]),
        zloss_weight=float(w["moe_zloss_weight"]),
    )


def hold_to_file(tr, cfg, sizes) -> None:
    """The program's config against what the config FILE states."""
    built = dict(
        vocab=cfg.vocab, d_model=cfg.d_model, n_layers=cfg.n_layers,
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, d_ff=cfg.d_ff,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps, n_experts=cfg.n_experts,
        top_k=cfg.moe_top_k, held=cfg.n_held, first=cfg.expert_first,
        pattern=cfg.pattern, aux_weight=cfg.moe_aux_weight,
        zloss_weight=cfg.moe_zloss_weight)
    for k, v in built.items():
        if v != sizes[k]:
            raise SystemExit(
                f"config {k}: the program builds {v}, the file states {sizes[k]}")
    if (cfg.expert_act, cfg.router_input, cfg.router_f32) != (
            "relu", "attn_norm", True):
        raise SystemExit("config: ReGLU experts behind a float32 router "
                         "that reads the attention-side norm are stated")
    if not tr.moe_counter_names(cfg):
        raise SystemExit("config: the step returns no routing counters")


class ShareTrainJob(train.TrainJob):
    def __init__(self, ctx):
        from tf_operator_tpu.models import transformer as tr

        needs = ("d_head", "layer_pattern", "experts_held", "router_input",
                 "expert_act", "router_f32")
        missing = [k for k in needs if k not in tr.CONFIG_OVERRIDE_FIELDS]
        if missing or not hasattr(tr, "lm_loss_with_counters"):
            raise SystemExit(
                "this program cannot build the configuration: its "
                f"transformer has no {missing or 'routing counters'} — no result")
        # the whole of the sizes, for TrainJob.check's reference call too
        ctx.sizes = self.sizes = model_sizes(ctx)
        hold_to_file(tr, tr.preset_from_workload(ctx.config["workload"]), self.sizes)

        self.counters: List[Dict[str, Any]] = []
        self.grad1 = None
        init = tr.init_transformer
        with swapped(tr, lm_loss=tr.lm_loss_with_counters,
                     init_transformer=lambda key, cfg: (
                         init(key, cfg), tr.zero_moe_counters(cfg))):
            super().__init__(ctx)  # trainer, compile, loader, the followed steps
        self.program["routed_here"] = [
            c["moe_routed_here"] for c in _host(self.counters[:self.followed])]
        self.program["grad1"] = self.grad1
        self.counters.clear()

    def _step(self) -> None:
        """``TrainJob._step``; the step's counters stay on the device (four
        scalars beside the loss) until the window is over. The next step
        is handed them as its donated ``extra`` but does not read it (the
        loss returns new ones), so jit drops that argument and these
        buffers stay alive; were they ever consumed, the fetch would raise."""
        super()._step()
        self.counters.append(self.state.extra)
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

    def window(self, seconds: float) -> Dict[str, Any]:
        samples = super().window(seconds)
        c = self.counters[:] = _host(self.counters)
        samples["counters"] = list(c)
        samples["model_sizes"] = self.sizes
        samples["notes"].update(
            routed_here_per_step=sum(x["moe_routed_here"] for x in c) / len(c),
            rows_per_held_expert_per_layer=sum(
                x["moe_held_load_mean"] for x in c) / len(c) / self.sizes["n_layers"],
        )
        return samples

    def traced_window(self) -> Dict[str, Any]:
        n0 = len(self.counters)
        out = super().traced_window()
        out["counters"] = self.counters[n0:] = _host(self.counters[n0:])
        return out

    def check(self, samples, control=None):
        """``TrainJob.check`` against this model's reference, with the
        router judged apart (``compare``)."""
        from benchmarks import reference, reference_smallthinker

        def noted(Check, program, ref, limits, prefix=""):
            gaps = diff_gaps(program["grad1"], ref["grad1"])
            self.ctx.say(f"note {prefix or 'program:'} grad1 difference by leaf: "
                         + ", ".join(f"{k}={v:.3e}" for k, v in sorted(gaps.items())))
            for what in ("grad1_norms", "change_norms"):
                self.ctx.say(
                    f"note {prefix or 'program:'} {what} gap by leaf: " + ", ".join(
                        f"{k}={abs(program[what][k] - v) / v:.2e}"
                        for k, v in sorted(ref[what].items())))
            return compare(Check, program, ref, limits, prefix, gaps)

        with swapped(reference, train_reference=reference_smallthinker.train_reference), \
                swapped(train, compare=noted):
            return super().check(samples, control)


def _host(counters) -> List[Dict[str, float]]:
    """Steps' counters as floats, in one fetch."""
    import jax

    return [{k: float(v) for k, v in c.items()} for c in jax.device_get(counters)]


# the leaves whose gradient the router's choices decide: a flipped near-tie
# moves a token's whole contribution from one expert to another
ROUTED = ("layers/w_router", "layers/w_gate", "layers/w_up", "layers/w_down",
          "layers/mlp_norm")


def diff_gaps(program, ref) -> Dict[str, float]:
    """|program's leaf - reference's leaf| over |reference's leaf|, Euclidean,
    for every leaf of the first gradient (host arrays by leaf name)."""
    import numpy as np

    return {k: float(np.linalg.norm((program[k] - r).ravel())
                     / np.linalg.norm(r.ravel())) for k, r in ref.items()}


def compare(Check, program, ref, limits, prefix="", gaps=None):
    """``runners/train.compare`` with the first gradient judged by the
    DIFFERENCE of each leaf from the reference's, not by the difference of
    their norms, and the router held by a count.

    The norm of a gradient moves only in the second order under rounding
    noise (|g + n| - |g| is about |n|^2 / 2|g| for noise across g), while
    near-ties of the top-6 that flip between any two computations move it
    in the first: by norms the program read up to 2.0e-3 off and the
    float8 control as little as 2.3e-3 (PERF.md section 6). The difference
    sees the rounding itself. It is read in two groups, by the worst leaf
    of each: the leaves in front of the router (embedding, attention), and
    the ROUTED leaves, which the flips reach in full.
    ``routed_step1_rel_gap`` holds the router's product to float32: the
    first step's count of choices routed to held experts (same weights on
    both sides) against the reference's. A product rounded to bfloat16
    makes exact ties of near-ties, the top-k gives a tie to the lower
    index, and the held experts are the lowest: the count rises by about
    half a percent, several times what unbiased flips move it."""
    gaps = diff_gaps(program["grad1"], ref["grad1"]) if gaps is None else gaps
    out = [
        Check(f"{prefix}loss_step{i + 1}_abs_gap", abs(p - r),
              limits[f"loss_step{i + 1}_abs_gap"]["limit"])
        for i, (p, r) in enumerate(zip(program["losses"], ref["losses"]))
    ]
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
    return out
