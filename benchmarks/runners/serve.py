"""Runner for serving mixes: open-loop requests through ``ServeEngine``.

Weights come from one jitted ``init_transformer`` call on the seed, the
engine is compiled ahead (``compile()``) and warmed by a few requests that
touch both programs, then ``run(requests, on_event=...)`` serves the
window's requests, each with its due time. The engine stamps ``arrival``,
``admitted``, ``first_token`` and ``token_times`` on its ``Request``
objects; everything reported is arithmetic on those stamps and on the
``step`` events.
"""

from __future__ import annotations

import gc
import math
import os
import sys
import threading
import time
import traceback
from typing import Any, Dict, List

import numpy as np

from benchmarks.stats import percentile


class _WindowClosed(Exception):
    """Raised from the step callback to end a run at the window's close."""


class _EventClock:
    """Stamps every engine event with the wall clock, this thread's CPU
    time and the time spent in garbage collection, so that a pause between
    two events says what the host was doing: computing, collecting, or
    asleep; a watchdog thread notes where the serving thread stands when a
    busy engine falls silent for a second."""

    def __init__(self):
        self.marks: List[tuple] = []
        self.gc_s, self._gc_t0 = 0.0, 0.0

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gc_s += time.perf_counter() - self._gc_t0

    def stamp(self, kind: str, engine_empty: bool = False) -> None:
        self.marks.append((kind, time.perf_counter(), time.thread_time(),
                           self.gc_s, engine_empty))

    def start(self, t_run: float) -> None:
        self.t_run = t_run
        gc.callbacks.append(self._on_gc)
        self.stamp("start", engine_empty=True)
        self.stalls: List[Dict[str, Any]] = []
        self._done = threading.Event()
        self._watch = threading.Thread(
            target=self._watchdog, args=(threading.get_ident(),), daemon=True)
        self._watch.start()

    def stop(self) -> None:
        self.stamp("stop")
        self._done.set()
        self._watch.join()
        gc.callbacks.remove(self._on_gc)

    def _watchdog(self, main_thread: int, after_s: float = 1.0) -> None:
        """Four times a second: has a busy engine been silent for ``after_s``?
        Then note, once for that pause, where the serving thread stands, how
        many cores the whole process keeps busy, and the host's load."""
        seen = None
        while not self._done.wait(0.25):
            last = self.marks[-1]
            if last is seen or last[4] or time.perf_counter() - last[1] < after_s:
                continue
            seen = last
            frame = sys._current_frames().get(main_thread)
            c0, t0 = time.process_time(), time.perf_counter()
            self._done.wait(0.2)
            cores = (time.process_time() - c0) / (time.perf_counter() - t0)
            try:
                with open("/proc/loadavg") as f:
                    load = f.read().split()[0]
            except OSError:
                load = None
            self.stalls.append({
                "silent_since_s": round(last[1] - self.t_run, 3), "after": last[0],
                "serving_thread_at": [
                    f"{os.path.basename(fs.filename)}:{fs.lineno} {fs.name}"
                    for fs in traceback.extract_stack(frame)[-4:]],
                "process_cores_busy": round(cores, 2), "host_load_1m": load,
            })

    def longest(self, k: int) -> List[Dict[str, Any]]:
        """The ``k`` longest pauses between consecutive events."""
        pairs = sorted(zip(self.marks, self.marks[1:]),
                       key=lambda ab: ab[0][1] - ab[1][1])[:k]
        return [{
            "at_s": round(b[1] - self.t_run, 3), "before": b[0],
            "engine_was_empty": a[4],  # then it slept until the next arrival
            "wall_ms": round(1e3 * (b[1] - a[1]), 1),
            "thread_cpu_ms": round(1e3 * (b[2] - a[2]), 1),
            "gc_ms": round(1e3 * (b[3] - a[3]), 1),
        } for a, b in pairs]


def setup(ctx):
    return ServeJob(ctx)


class ServeJob:
    def __init__(self, ctx):
        import jax

        from tf_operator_tpu.models.transformer import (
            init_transformer,
            preset_from_workload,
        )
        from tf_operator_tpu.serve.engine import ServeConfig, ServeEngine

        self.ctx = ctx
        wl = ctx.config["workload"]
        cfg = preset_from_workload(wl)
        for k in ("vocab", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_ff"):
            if getattr(cfg, k) != ctx.sizes[k]:
                raise SystemExit(
                    f"config {k}: the program builds {getattr(cfg, k)}, the "
                    f"file states {ctx.sizes[k]}")
        self.cfg = cfg
        self.scfg = ServeConfig(
            page_size=int(wl["kv_page_size"]), pool_pages=int(wl["kv_pool_pages"]),
            max_slots=int(wl["max_slots"]), prefill_chunk=int(wl["prefill_chunk"]),
        )
        t0 = time.perf_counter()
        with jax.default_device(ctx.devices[0]):
            params = jax.jit(lambda k: init_transformer(k, cfg))(
                jax.random.PRNGKey(ctx.seed))
            self.engine = ServeEngine(cfg, params, self.scfg)
            del params
            compiled = self.engine.compile()
        ctx.say(f"note engine_compile: {compiled!r} init+compile_s="
                f"{time.perf_counter() - t0!r}")
        # warm both programs, the pool fill and the small host->device
        # transfers: prompts of one, two and three chunks, a few decode steps
        c = self.scfg.prefill_chunk
        warm = [{"rid": -1 - i, "prompt": [1 + i] * n, "max_new": 3, "arrival": 0.0}
                for i, n in enumerate((c // 2, c + 1, 2 * c + 1))]
        self.serve(warm, float("inf"), stop_at_close=False)

    # -- one engine run ---------------------------------------------------------

    def serve(self, reqs: List[Dict[str, Any]], seconds: float,
               stop_at_close: bool) -> Dict[str, Any]:
        from jax.profiler import TraceAnnotation

        from tf_operator_tpu.serve.engine import Request

        requests = [Request(rid=r["rid"], prompt=list(r["prompt"]),
                            max_new=int(r["max_new"]), arrival=float(r["arrival"]))
                    for r in reqs]
        steps: List[tuple] = []  # (wall time, generated so far, free pages)
        clock = _EventClock()
        span = [TraceAnnotation("bench.engine_step")]
        span[0].__enter__()

        def on_event(kind, payload):
            if kind != "step":
                return clock.stamp(kind)
            clock.stamp(kind, engine_empty=payload["active"] == 0)
            now = time.perf_counter() - t_run
            steps.append((now, payload["generated"], payload["free_pages"]))
            span[0].__exit__(None, None, None)
            if stop_at_close and now >= seconds:
                raise _WindowClosed
            span[0] = TraceAnnotation("bench.engine_step")
            span[0].__enter__()

        result = None
        t_run = time.perf_counter()
        clock.start(t_run)
        try:
            with TraceAnnotation("bench.engine_run"):
                result = self.engine.run(  # every stamp is the benchmark's clock
                    requests, clock=time.perf_counter, on_event=on_event)
            span[0].__exit__(None, None, None)
        except _WindowClosed:
            pass
        finally:
            clock.stop()
        return {"requests": requests, "steps": steps, "result": result,
                "wall_s": time.perf_counter() - t_run,
                "longest_gaps": clock.longest(3), "gc_s": clock.gc_s,
                "stalls": clock.stalls}

    def summarise(self, run, seconds: float) -> Dict[str, Any]:
        reqs = run["requests"]
        finished = [r for r in reqs if r.finished >= 0]
        in_window = sum(1 for r in reqs for t in r.token_times if t <= seconds)
        ttft = [(r.first_token - r.arrival) if r.first_token >= 0 else math.inf
                for r in reqs]
        itl = [b - a for r in reqs for a, b in zip(r.token_times, r.token_times[1:])]
        tpot = [(r.token_times[-1] - r.token_times[0]) / (len(r.token_times) - 1)
                for r in finished if len(r.token_times) > 1]
        decoded = [(t, g) for t, g, _ in run["steps"]]
        step_gaps = [t1 - t0 for (t0, g0), (t1, g1) in zip(decoded, decoded[1:])
                     if g1 > g0]
        res = run["result"]
        return {
            "seconds": seconds, "wall_s": run["wall_s"],
            "tokens_in_window": in_window, "ttft_s": ttft, "itl_s": itl,
            "tpot_s": tpot,
            "queue_wait_s": [r.admitted - r.arrival for r in reqs if r.admitted >= 0],
            "engine_step_s": step_gaps,
            "latency_s": [r.finished - r.arrival for r in finished],
            "per_token_s": [(r.finished - r.arrival) / len(r.tokens) for r in finished],
            "longest_gaps": run["longest_gaps"], "gc_s": run["gc_s"],
            "stalls": run["stalls"],
            "min_free_pages": min((f for _, _, f in run["steps"]),
                                  default=self.scfg.pool_pages),
            "pool_pages": self.scfg.pool_pages,
            "page_leaks": None if res is None
            else res.free_pages_start - res.free_pages_end,
            "finished": [(r.rid, list(r.prompt), list(r.tokens)) for r in finished],
            "prefill_chunk": self.scfg.prefill_chunk,
            "attempted": len(reqs),
            "unfinished": len(reqs) - len(finished),
        }

    # -- the harness's calls ------------------------------------------------------

    def window(self, seconds: float) -> Dict[str, Any]:
        from benchmarks import traffic

        mix = self.ctx.mix
        reqs = traffic.requests(self.ctx.seed, self.cfg.vocab, mix, seconds)
        stop = bool(mix.get("stop_at_close", False))
        s = self.summarise(self.serve(reqs, seconds, stop), seconds)
        # a request never started counts as missing; one cut off by the
        # window's close in an above-capacity mix is not a failure
        s["failed"] = 0 if stop else s["unfinished"]
        s["notes"] = {
            "requests": s["attempted"], "unfinished_at_close": s["unfinished"],
            "run_wall_s": s["wall_s"],
            "tokens_per_s_over_run": sum(len(t) for _, _, t in s["finished"]) / s["wall_s"],
            "engine_steps": len(s["engine_step_s"]),
            "gc_in_window_s": s["gc_s"],
            "longest_pauses_between_engine_events": s["longest_gaps"],
            "pauses_over_1s_of_a_busy_engine": s["stalls"],
        }
        return s

    def traced_window(self) -> Dict[str, Any]:
        from benchmarks import traffic

        seconds = float(self.ctx.mix.get("trace_seconds", 4.0))
        reqs = traffic.requests(self.ctx.seed + 1, self.cfg.vocab, self.ctx.mix, seconds)
        return self.summarise(self.serve(reqs, seconds, stop_at_close=False), seconds)

    def end_to_end(self, s) -> Dict[str, float]:
        """Every number a cell may name as an end-to-end metric (each run
        prints them all): quantiles over ALL requests, gaps or finished
        requests of the window, in ms; nothing to take one from reads inf."""
        out = {"serve_tokens_per_s": s["tokens_in_window"] / s["seconds"]}
        for name, xs in (("ttft", s["ttft_s"]), ("itl", s["itl_s"]),
                         ("tpot", s["tpot_s"]), ("latency", s["latency_s"]),
                         ("per_token", s["per_token_s"])):
            for q in (50, 95):
                out[f"{name}_p{q}_ms"] = 1e3 * percentile(xs, q / 100) if xs else math.inf
            out[f"{name}_mean_ms"] = 1e3 * sum(xs) / len(xs) if xs else math.inf
        return out

    def release(self) -> None:
        self.engine = None
        gc.collect()

    def check(self, s, control=None):
        """A seeded sample of the finished requests, the longest among them:
        every served token against the reference's teacher-forced logits.
        ``control`` (a precision, or several) adds the same numbers for the
        tokens that precision puts first at the same positions (names
        prefixed ``control.<precision>:``)."""
        from benchmarks import reference

        ctx, limits = self.ctx, self.ctx.config["limits"]
        sample = pick_sample(s["finished"], ctx.seed, int(ctx.mix.get("check_requests", 8)))
        if not sample:
            return [ctx.Check("no_finished_request_to_compare", 1.0, 0.0)]
        controls = [control] if isinstance(control, str) else list(control or [])
        t0 = time.perf_counter()
        w = reference.init_weights(ctx.seed, ctx.sizes)
        found: Dict[Any, List[np.ndarray]] = {}
        for _, prompt, tokens in sample:
            ref, *low = (reference.served_logits(
                w, ctx.sizes, prompt, tokens, pad_to=self.cfg.max_seq,
                rows=int(ctx.mix["output_len"]["max"]), precision=p)
                for p in ["float32"] + controls)
            found.setdefault(None, []).append(reference.gaps(ref, tokens))
            for c, lg in zip(controls, low):
                found.setdefault(c, []).append(reference.gaps(ref, lg.argmax(-1)))
        del w
        gaps = {k: np.concatenate(v) for k, v in found.items()}
        ctx.say(f"note reference_s: {time.perf_counter() - t0!r} over "
                f"{len(sample)} requests, {gaps[None].size} served tokens; "
                + "; ".join(f"exact{'' if k is None else ' (' + k + ')'}="
                            f"{int((g == 0).sum())}" for k, g in gaps.items()))
        out = []
        for judged_by, g in gaps.items():
            prefix = "" if judged_by is None else f"control.{judged_by}:"
            out += [
                ctx.Check(prefix + "served_logit_gap_max", float(g.max()),
                          limits["served_logit_gap_max"]["limit"]),
                ctx.Check(prefix + "served_logit_gap_mean", float(g.mean()),
                          limits["served_logit_gap_mean"]["limit"]),
            ]
        if s["page_leaks"] is not None:
            out.append(ctx.Check("kv_page_leaks", float(s["page_leaks"]), 0.0))
        return out


def pick_sample(finished, seed: int, k: int):
    """``k`` finished requests drawn from the seed, the longest one always."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i][1]) + len(finished[i][2]))
    rest = [i for i in range(len(finished)) if i != longest]
    rng = np.random.default_rng(seed)
    picked = list(rng.permutation(rest)[: max(0, k - 1)])
    return [finished[i] for i in [longest] + picked]
