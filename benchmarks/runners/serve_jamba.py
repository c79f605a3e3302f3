"""Runner for the Jamba serving mixes: ``runners/serve.py``'s job (open-loop
requests through ``ServeEngine.run``) with the reference of this architecture
(``benchmarks/reference_jamba.py``) deciding ``correct``, and with what the
per-layer readers need beside the samples: the sizes of the model as run, the
bytes of the two kinds of sequence state, the engine's counters and the work
each window completed.

Served tokens see the recurrent state only through an argmax, under the
rounding of every product before it. ``state_path_rel_gap`` sees it alone:
the longest checked request's tokens through the program's own state path —
``selective_scan_chunk`` a prefill chunk at a time out of and into a slot of
a ``StateStore``, ``selective_scan_step`` on the whole store a served token
at a time, as ``ServeEngine``'s two programs call them — on the float32
inputs the REFERENCE's first Mamba layer makes, against the state the
reference's token-by-token scan leaves.

``engine_state_rel_gap`` is the same state as ``ServeEngine`` ITSELF leaves
it: before the engine goes, the window's longest prompt is served alone
through ``engine.run`` and the first Mamba layer's state read out of its slot,
against the reference's float32 scan of the inputs the reference makes when
it multiplies as the config states the program does (``precision="stated"``:
the backend's DEFAULT) — against float32 products the engine's own rounding
stands as far off as a bfloat16 state does — over the state's slow elements
(``check`` says why). That run drains, so it is also
the one held to zero page leaks: a window closed from the step callback has
no drained pool to count.

The TRACED window is the cell's regime and nothing else: ONE closed run of
``trace_after_s + trace_seconds`` of the mix's arrivals, of which the trace
holds the last ``trace_seconds`` — every slot decoding, arrivals waiting for a
slot, as the measured window stands from about its 10th second on. The
profiler session the harness opened is ended at once and another opened in
its directory from the step callback (a run of this model is 28 unrolled
layers, and writing and reducing a trace of 1,650 of them took half an hour;
my chip run, PR 47), under a window annotation of its own: the harness's was
opened under the first session and is in neither trace. What the readers need
of that part is taken from what a cut run still has: the engine's LIVE
counters (they ride every ``step`` event), differenced over the traced part,
and the page pool's peak from its ``step`` events' free pages. The accepted
readers of the closing ``serve.counters`` span (``engine_runs_ahead_share``,
``chunk_carries_decode_share``) read the whole run's totals.

A program that has no Mamba layer (the parent of the PR that brought this
cell) ends here, before anything is built, with "no result".
"""

from __future__ import annotations

import time
from contextlib import ExitStack
from dataclasses import asdict
from functools import partial
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks.runners.serve import ServeJob, percentile, pick_sample  # noqa: F401 — sweep.py reads percentile off the runner

MAMBA_KEYS = ("mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank")
ALONE_TOKENS = 256  # served tokens of the request ``engine_state_rel_gap`` reads


def model_sizes(ctx) -> Dict[str, Any]:
    """The reference's and the FLOP counts' ``sizes``: the harness's group
    plus the Mamba mixer's and one period of the layer pattern — layer i
    attends iff ``i % attn_layer_period == attn_layer_offset`` — from the
    config file's own (published) keys."""
    config = ctx.config
    period, offset = int(config["attn_layer_period"]), int(config["attn_layer_offset"])
    if ctx.sizes["n_layers"] % period:
        raise SystemExit(f"config num_hidden_layers: not whole periods of {period}")
    if int(config["num_experts"]) != 1:
        raise SystemExit("config num_experts: this runner serves the dense model")
    return dict(ctx.sizes,
                pattern=tuple("attn" if j == offset else "mamba" for j in range(period)),
                **{k: int(config[k]) for k in MAMBA_KEYS})


def state_path_programs(store, c: int, slot: int):
    """The two calls ``ServeEngine``'s programs make on the FIRST Mamba
    layer's state, each a jitted function of the store's state array
    (donated, as the engine donates it), the layer's A and D, and a
    sequence's rows (u, delta, B, C): ``chunk(st, A, D, start, n_valid,
    *rows)`` — ``c`` rows from ``start`` through ``selective_scan_chunk`` out
    of and into ``slot``, zeros where ``start == 0``, rows past ``n_valid``
    invalid — and ``step(st, A, D, i, *rows)`` — row ``i`` through
    ``selective_scan_step`` on the whole store with every other slot inactive
    and steered to the trash slot."""
    import jax
    import jax.numpy as jnp

    from tf_operator_tpu.ops.selective_scan import (
        selective_scan_chunk,
        selective_scan_step,
    )
    from tf_operator_tpu.serve.kvcache import read_slot, write_slot

    s_n = store.slots

    @partial(jax.jit, donate_argnums=0)
    def chunk(st, A, D, start, n_valid, *rows):
        part = [jax.lax.dynamic_slice_in_dim(a, start, c) for a in rows]
        _, s1 = selective_scan_chunk(*part, A, D, read_slot(st, 0, slot, start == 0)[0],
                                     valid=jnp.arange(c) < n_valid)
        return write_slot(st, 0, slot, s1[None])

    @partial(jax.jit, donate_argnums=0)
    def step(st, A, D, i, *rows):
        active = jnp.arange(s_n) == slot
        one = [jnp.zeros((s_n,) + a.shape[1:], a.dtype).at[slot].set(a[i]) for a in rows]
        return selective_scan_step(
            *one, A, D, st, valid=active, layer=0,
            slots=jnp.where(active, jnp.arange(s_n), store.trash_slot))[1]

    return chunk, step


def setup(ctx):
    from tf_operator_tpu.models import transformer

    wl = ctx.config["workload"]
    if (wl["preset"] not in transformer.PRESETS
            or "mamba_d_state" not in transformer.CONFIG_OVERRIDE_FIELDS):
        raise SystemExit(
            f"this program has no preset {wl['preset']!r} (no Mamba layer in "
            "models/transformer.py): the cell cannot run — no result")
    return JambaServeJob(ctx)


class JambaServeJob(ServeJob):
    trace_dir = None  # where ``retrace`` writes: the harness's, in a traced window

    def __init__(self, ctx):
        super().__init__(ctx)
        self.sizes = model_sizes(ctx)
        cfg, store = self.cfg, self.engine.store
        for k in MAMBA_KEYS:
            if getattr(cfg, k) != self.sizes[k]:
                raise SystemExit(f"config {k}: the program builds {getattr(cfg, k)}, "
                                 f"the file states {self.sizes[k]}")
        if [("mamba" if k == "mamba" else "attn") for k in cfg.pattern] \
                != list(self.sizes["pattern"]):
            raise SystemExit(f"config attn_layer_*: the program's period is {cfg.pattern}")
        self.cache = {  # bytes of the two kinds of sequence state
            "page_bytes": 2 * 4 * self.sizes["pattern"].count("attn")
            * (cfg.n_layers // len(cfg.pattern)) * self.scfg.page_size
            * cfg.n_kv_heads * cfg.head_dim,
            "state_slot_bytes": store.slot_bytes,
            "state_store_bytes": store.bytes,
        }

    def program_state(self, inputs, n_prompt: int):
        """(u, delta, B, C, A, D) of one sequence, rows [0, n_prompt) its
        prompt -> the state [d_state, channels] the program's state path
        leaves in the sequence's slot: prefill chunks of the engine's size
        (the last one short, its padding rows invalid) carried through the
        slot, then one step a remaining row."""
        import jax.numpy as jnp

        from tf_operator_tpu.serve.kvcache import StateStore

        store = StateStore.for_model(self.cfg, self.scfg.max_slots)
        c, slot = self.scfg.prefill_chunk, store.slots - 1
        chunk, step = state_path_programs(store, c, slot)
        *rows, A, D = inputs
        pad = -n_prompt % c
        padded = [jnp.concatenate([a[:n_prompt], jnp.zeros((pad,) + a.shape[1:], a.dtype)])
                  for a in rows]
        st = store.fresh()[0]
        for start in range(0, n_prompt, c):
            st = chunk(st, A, D, jnp.int32(start), jnp.int32(min(c, n_prompt - start)),
                       *padded)
        for i in range(n_prompt, rows[0].shape[0]):
            st = step(st, A, D, jnp.int32(i), *rows)
        return st[0, slot, 0].astype(jnp.float32)

    def serve(self, reqs, seconds: float, stop_at_close: bool,
              trace_from: Optional[float] = None) -> Dict[str, Any]:
        """``ServeJob.serve`` that also keeps the engine's live counters (a
        run closed from the step callback returns no ``RunResult``) and, of a
        run that drained, the pages its pool did not get back. With
        ``trace_from``: at the first step boundary past that second a
        profiler session is opened (``retrace``), and the counters and the
        step count as they stood there are kept beside the last ones."""
        kept: Dict[str, Any] = {"steps": 0}
        engine_run = self.engine.run

        def run(requests, clock, on_event):
            t0 = clock()

            def heard(kind, payload):
                if kind == "step":
                    kept["counters"] = payload["counters"]
                    kept["steps"] += 1
                    if (trace_from is not None and "traced_from" not in kept
                            and clock() - t0 >= trace_from):
                        kept["traced_from"] = (kept["steps"], asdict(payload["counters"]))
                        traced.enter_context(self.retrace())
                on_event(kind, payload)

            with ExitStack() as traced:  # the window annotation ends with the run
                return engine_run(requests, clock=clock, on_event=heard)

        self.engine.run = run
        try:
            served = super().serve(reqs, seconds, stop_at_close)
        finally:
            del self.engine.run  # the instance's wrapper: the class's method is back
        served["counters"] = kept.get("counters")
        served["traced_from"] = kept.get("traced_from", (0, {}))
        if served["result"] is not None:
            res = served["result"]
            self.page_leaks = res.free_pages_start - res.free_pages_end
        return served

    def retrace(self):
        """A profiler session in the directory of the one ``traced_window``
        ended (none off the harness: a rehearsal), and the window annotation
        the reducers clip to, to be entered."""
        import jax
        from jax.profiler import TraceAnnotation

        from benchmarks import trace_reduce

        if self.trace_dir is not None:
            jax.profiler.start_trace(self.trace_dir)
        return TraceAnnotation(trace_reduce.WINDOW_ANNOTATION)

    def traced_window(self) -> Dict[str, Any]:
        import jax
        from jax._src.profiler import _profile_state  # a runner is handed no path

        from benchmarks import traffic

        mix = self.ctx.mix
        after, seconds = float(mix["trace_after_s"]), float(mix.get("trace_seconds", 4.0))
        reqs = traffic.requests(self.ctx.seed + 1, self.cfg.vocab, mix, after + seconds)
        self.trace_dir = _profile_state.log_dir
        if self.trace_dir is not None:
            jax.profiler.stop_trace()
        try:
            run = self.serve(reqs, after + seconds, stop_at_close=True, trace_from=after)
        finally:  # the harness stops what it started, whatever happened here
            if self.trace_dir is not None and _profile_state.log_dir is None:
                jax.profiler.start_trace(self.trace_dir)
        return self.summarise(run, after + seconds)

    def summarise(self, run, seconds: float) -> Dict[str, Any]:
        s = super().summarise(run, seconds)
        s["model_sizes"] = self.sizes
        s["cache"] = self.cache
        # of a run traced from a later step on: the traced part's
        steps_before, before = run["traced_from"]
        s["engine_counters"] = run["counters"] and {
            k: v - before.get(k, 0) for k, v in asdict(run["counters"]).items()}
        s["pool_peak_in_use"] = s["pool_pages"] - min(
            (f for _, _, f in run["steps"][steps_before:]), default=s["pool_pages"])
        # what the window completed: a prompt whose prefill ended inside it,
        # and every output token stamped inside it
        s["window_work"] = [
            (len(r.prompt) if 0 <= r.first_token <= seconds else 0,
             sum(1 for t in r.token_times if t <= seconds))
            for r in run["requests"]]
        return s

    def served_alone(self):
        """The window's longest prompt and its first ``ALONE_TOKENS`` tokens
        through ``engine.run`` with nobody beside it -> (the tokens the state
        has seen, the first Mamba layer's state [d_state, channels] as the
        engine left it in the sequence's slot: the first, in an empty
        engine)."""
        from benchmarks import traffic

        ctx = self.ctx
        longest = max(traffic.requests(ctx.seed, self.cfg.vocab, ctx.mix, ctx.seconds),
                      key=lambda r: len(r["prompt"]))
        served = self.serve(
            [dict(longest, arrival=0.0, max_new=min(longest["max_new"], ALONE_TOKENS))],
            float("inf"), stop_at_close=False)
        (req,) = served["requests"]
        return (list(req.prompt) + req.tokens[:-1],
                np.asarray(served["result"].state[0][0, 0, 0]))

    def release(self) -> None:
        self.alone = self.served_alone()
        super().release()

    def check(self, s, control=None) -> List[Any]:
        """``runners/serve.py``'s comparison — a seeded sample of the finished
        requests, the longest among them, every served token against the
        reference's teacher-forced logits — against THIS architecture's
        reference, the state path alone over the longest request, and what
        ``served_alone`` left: the engine's own state and a drained pool.
        ``control``: a reference precision (or several) put in the program's
        place, names prefixed ``control.<precision>:``."""
        from benchmarks import reference_jamba as reference

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
        inputs, state = reference.mamba_state(w, self.sizes, seq)
        states = {None: self.program_state(inputs, len(prompt)).T,
                  **{c: reference.mamba_state(w, self.sizes, seq, c)[1] for c in controls}}
        state_gap = {k: float(np.linalg.norm(np.asarray(s1 - state))
                           / np.linalg.norm(np.asarray(state)))
                     for k, s1 in states.items()}
        # ... and as the ENGINE left it, over the request served alone: against
        # the scan of what the reference makes when it multiplies as stated,
        # over the state's SLOW elements (a token's decay above 0.99 at the
        # reference's mean step). The engine's products and the reference's are
        # the same products summed in another order, and a last-bit difference
        # now and then flips the bfloat16 rounding of an operand of the NEXT
        # product: one token's steps move by ~1e-3, which the whole state's
        # norm reads when the token is among the last (1.5e-3 on one seed in
        # nineteen, my chip runs, PR 47) and an element that holds hundreds of
        # tokens does not, while a bfloat16 state's rounding adds up there
        seq_e, got = self.alone
        made, want = reference.mamba_state(w, self.sizes, seq_e, "stated")
        slow = np.exp(np.asarray(made[1]).mean(0)[:, None] * np.asarray(made[4])) > 0.99

        def off(s1, where=slow):
            return float(np.linalg.norm(np.asarray(s1 - want)[where])
                         / np.linalg.norm(np.asarray(want)[where]))

        engine_gap = {None: off(got.T), **{
            c: off(reference.selective_scan(*made, round_state=True)[1]
                   if c == "state_bf16" else reference.mamba_state(w, self.sizes, seq_e, c)[1])
            for c in controls}}
        whole = off(got.T, np.ones_like(slow))
        del w, inputs, state, states, made, want
        gaps = {k: np.concatenate(v) for k, v in found.items()}
        ctx.say(f"note reference_s: {time.perf_counter() - t0!r} over "
                f"{len(sample)} requests (longest {max(len(p) + len(t) for _, p, t in sample)} "
                f"tokens), {gaps[None].size} served tokens; "
                + "; ".join(f"exact{'' if k is None else ' (' + k + ')'}="
                            f"{int((g == 0).sum())}" for k, g in gaps.items())
                + "".join(f"; teacher_forced_logit_abs_gap_max ({c})={v!r}"
                          for c, v in logit_moved.items())
                + f"; state path over {len(seq)} tokens, {len(prompt)} of them prefilled; "
                f"the engine's state over {len(seq_e)} tokens, {int(slow.sum())} slow elements "
                f"(over all of it {whole!r})")
        out = []
        for judged_by, g in gaps.items():
            prefix = "" if judged_by is None else f"control.{judged_by}:"
            out += [ctx.Check(prefix + name, float(value), limits[name]["limit"])
                    for name, value in (("served_logit_gap_max", g.max()),
                                        ("served_logit_gap_mean", g.mean()),
                                        ("state_path_rel_gap", state_gap[judged_by]),
                                        ("engine_state_rel_gap", engine_gap[judged_by]))]
        # the run served alone drained its pool (the windows are cut at their close)
        out.append(ctx.Check("kv_page_leaks", float(self.page_leaks), 0.0))
        return out
