"""Runner for the Olmo-Hybrid serving mixes: ``runners/serve.py``'s job
(open-loop requests through ``ServeEngine.run``) with the reference of this
architecture (``benchmarks/reference_olmo_hybrid.py``) deciding ``correct``,
and with what the new per-layer readers need beside the samples: the sizes
of the model as run, the bytes of the two kinds of sequence state, the
engine's counters and the work each window completed.

Served tokens see the recurrent state only through an argmax, under the
rounding of every product before it. ``state_path_rel_gap`` sees it alone:
the longest checked request's tokens through the program's own state path —
``gated_delta_chunk`` a prefill chunk at a time out of and into a slot of a
``StateStore``, ``gated_delta_step`` on the whole store a served token at a
time, as ``ServeEngine``'s two programs call them — on the float32 inputs
the REFERENCE's first linear layer makes, against the state the reference's
token-by-token scan leaves.

A program that has no linear-attention layer (the parent of the PR that
brought this cell) ends here, before anything is built, with "no result".
"""

from __future__ import annotations

import time
from dataclasses import asdict
from functools import partial
from typing import Any, Dict, List

import numpy as np

from benchmarks.runners.serve import ServeJob, percentile, pick_sample  # noqa: F401 — sweep.py reads percentile off the runner

HF_TO_LINEAR = {"lin_heads": "linear_num_key_heads", "lin_dk": "linear_key_head_dim",
                "lin_dv": "linear_value_head_dim", "lin_conv": "linear_conv_kernel_dim"}
KINDS = {"linear_attention": "linear", "full_attention": "full"}


def model_sizes(ctx) -> Dict[str, Any]:
    """The reference's and the FLOP counts' ``sizes``: the harness's group
    plus the linear mixer's and one period of the layer pattern, from the
    config file's own (published) keys."""
    config = ctx.config
    kinds = [KINDS[k] for k in config["layer_types"][: ctx.sizes["n_layers"]]]
    period = kinds[: kinds.index("full") + 1]
    if kinds != period * (len(kinds) // len(period)):
        raise SystemExit(f"config layer_types: not whole periods of {period}")
    return dict(ctx.sizes, pattern=tuple(period),
                **{k: int(config[v]) for k, v in HF_TO_LINEAR.items()})


def state_path_programs(store, c: int, slot: int):
    """The two calls ``ServeEngine``'s programs make on the FIRST linear
    layer's state, each a jitted function of the store's state array
    (donated, as the engine donates it) and a sequence's rows (q, k, v,
    alpha_log, beta): ``chunk(st, start, n_valid, *rows)`` — ``c`` rows from
    ``start`` through ``gated_delta_chunk`` out of and into ``slot``, zeros
    where ``start == 0``, rows past ``n_valid`` invalid — and ``step(st, i,
    *rows)`` — row ``i`` through ``gated_delta_step`` on the whole store with
    every other slot inactive and steered to the trash slot."""
    import jax
    import jax.numpy as jnp

    from tf_operator_tpu.ops.gated_delta import gated_delta_chunk, gated_delta_step
    from tf_operator_tpu.serve.kvcache import read_slot, write_slot

    s_n = store.slots

    @partial(jax.jit, donate_argnums=0)
    def chunk(st, start, n_valid, *rows):
        part = [jax.lax.dynamic_slice_in_dim(a, start, c) for a in rows]
        _, s1 = gated_delta_chunk(*part, read_slot(st, 0, slot, start == 0),
                                  valid=jnp.arange(c) < n_valid)
        return write_slot(st, 0, slot, s1)

    @partial(jax.jit, donate_argnums=0)
    def step(st, i, *rows):
        active = jnp.arange(s_n) == slot
        one = [jnp.zeros((s_n,) + a.shape[1:], a.dtype).at[slot].set(a[i]) for a in rows]
        return gated_delta_step(
            *one, st, valid=active, layer=0,
            slots=jnp.where(active, jnp.arange(s_n), store.trash_slot))[1]

    return chunk, step


def setup(ctx):
    from tf_operator_tpu.models import transformer

    wl = ctx.config["workload"]
    if (wl["preset"] not in transformer.PRESETS
            or "lin_heads" not in transformer.CONFIG_OVERRIDE_FIELDS):
        raise SystemExit(
            f"this program has no preset {wl['preset']!r} (no linear-attention "
            "layer in models/transformer.py): the cell cannot run — no result")
    return HybridServeJob(ctx)


class HybridServeJob(ServeJob):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.sizes = model_sizes(ctx)
        cfg, store = self.cfg, self.engine.store
        for k, v in HF_TO_LINEAR.items():
            if getattr(cfg, k) != self.sizes[k]:
                raise SystemExit(f"config {v}: the program builds {getattr(cfg, k)}, "
                                 f"the file states {self.sizes[k]}")
        if [("linear" if k == "linear" else "full") for k in cfg.pattern] \
                != list(self.sizes["pattern"]):
            raise SystemExit(f"config layer_types: the program's period is {cfg.pattern}")
        self.cache = {  # bytes of the two kinds of sequence state
            "page_bytes": 2 * 4 * cfg.n_of_kind(False) * self.scfg.page_size
            * cfg.n_kv_heads * cfg.head_dim,
            "state_slot_bytes": store.slot_bytes,
            "state_store_bytes": store.bytes,
        }

    def program_state(self, inputs, n_prompt: int):
        """(q, k, v, alpha, beta) of one sequence, rows [0, n_prompt) its
        prompt -> the state [H, d_k, d_v] the program's state path leaves in
        the sequence's slot: prefill chunks of the engine's size (the last
        one short, its padding rows invalid) carried through the slot, then
        one step a remaining row."""
        import jax.numpy as jnp

        from tf_operator_tpu.serve.kvcache import StateStore

        store = StateStore.for_model(self.cfg, self.scfg.max_slots)
        c, slot = self.scfg.prefill_chunk, store.slots - 1
        chunk, step = state_path_programs(store, c, slot)
        q, k, v, alpha, beta = inputs
        rows = (q, k, v, jnp.log(alpha), beta)
        pad = -n_prompt % c
        padded = [jnp.concatenate([a[:n_prompt], jnp.zeros((pad,) + a.shape[1:], a.dtype)])
                  for a in rows]
        st = store.fresh()[0]
        for start in range(0, n_prompt, c):
            st = chunk(st, jnp.int32(start), jnp.int32(min(c, n_prompt - start)), *padded)
        for i in range(n_prompt, q.shape[0]):
            st = step(st, jnp.int32(i), *rows)
        return st[0, slot].astype(jnp.float32)

    def summarise(self, run, seconds: float) -> Dict[str, Any]:
        s = super().summarise(run, seconds)
        res = run["result"]
        s["model_sizes"] = self.sizes
        s["cache"] = self.cache
        s["engine_counters"] = None if res is None else asdict(res.counters)
        s["pool_peak_in_use"] = None if res is None else res.pool_peak_in_use
        # what the window completed: a prompt whose prefill ended inside it,
        # and every output token stamped inside it, with its context length
        s["window_work"] = [
            (len(r.prompt) if 0 <= r.first_token <= seconds else 0,
             sum(1 for t in r.token_times if t <= seconds))
            for r in run["requests"]]
        return s

    def check(self, s, control=None) -> List[Any]:
        """``runners/serve.py``'s comparison — a seeded sample of the finished
        requests, the longest among them, every served token against the
        reference's teacher-forced logits — against THIS architecture's
        reference. ``control``: a reference precision (or several) put in the
        program's place, names prefixed ``control.<precision>:``."""
        from benchmarks import reference_olmo_hybrid as reference

        ctx, limits = self.ctx, self.ctx.config["limits"]
        sample = pick_sample(s["finished"], ctx.seed, int(ctx.mix.get("check_requests", 8)))
        if not sample:
            return [ctx.Check("no_finished_request_to_compare", 1.0, 0.0)]
        controls = [control] if isinstance(control, str) else list(control or [])
        t0 = time.perf_counter()
        w = reference.init_weights(ctx.seed, self.sizes)
        found: Dict[Any, List[np.ndarray]] = {}
        logit_moved = {c: 0.0 for c in controls}  # a control that did nothing reads 0
        for _, prompt, tokens in sample:
            ref, *low = (reference.served_logits(
                w, self.sizes, prompt, tokens, pad_to=self.cfg.max_seq,
                rows=int(ctx.mix["output_len"]["max"]), precision=p)
                for p in ["float32"] + controls)
            found.setdefault(None, []).append(reference.gaps(ref, tokens))
            for c, lg in zip(controls, low):
                found.setdefault(c, []).append(reference.gaps(ref, lg.argmax(-1)))
                logit_moved[c] = max(logit_moved[c], float(np.abs(lg - ref).max()))
        # the state alone, over the longest request (pick_sample's first)
        _, prompt, tokens = sample[0]
        seq = list(prompt) + list(tokens[:-1])
        inputs, state = reference.linear_state(w, self.sizes, seq)
        states = {None: self.program_state(inputs, len(prompt)),
                  **{c: reference.linear_state(w, self.sizes, seq, c)[1] for c in controls}}
        state_gap = {k: float(np.linalg.norm(np.asarray(s1 - state))
                           / np.linalg.norm(np.asarray(state)))
                     for k, s1 in states.items()}
        del w, inputs, state, states
        gaps = {k: np.concatenate(v) for k, v in found.items()}
        ctx.say(f"note reference_s: {time.perf_counter() - t0!r} over "
                f"{len(sample)} requests (longest {max(len(p) + len(t) for _, p, t in sample)} "
                f"tokens), {gaps[None].size} served tokens; "
                + "; ".join(f"exact{'' if k is None else ' (' + k + ')'}="
                            f"{int((g == 0).sum())}" for k, g in gaps.items())
                + "".join(f"; teacher_forced_logit_abs_gap_max ({c})={v!r}"
                          for c, v in logit_moved.items())
                + f"; state path over {len(seq)} tokens, {len(prompt)} of them prefilled")
        out = []
        for judged_by, g in gaps.items():
            prefix = "" if judged_by is None else f"control.{judged_by}:"
            out += [ctx.Check(prefix + name, float(value), limits[name]["limit"])
                    for name, value in (("served_logit_gap_max", g.max()),
                                        ("served_logit_gap_mean", g.mean()),
                                        ("state_path_rel_gap", state_gap[judged_by]))]
        # a window closed at its end has no drained pool to count; the traced
        # window runs to its last request
        for name, run in (("kv_page_leaks", s), ("kv_page_leaks.traced", s.get("traced"))):
            if run and run["page_leaks"] is not None:
                out.append(ctx.Check(name, float(run["page_leaks"]), 0.0))
        return out
