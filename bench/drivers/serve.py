"""Driver ``serve``: requests decoded at the exit the GRLE scheduler picks.

The system under test is ``EdgeServingEngine.serve_slot(requests,
decode=True)``: one call prices a slot of up to ``batch_slots`` requests
with the GRLE scheduler and greedy-decodes each exit group through the
KV cache. The benchmark makes the weights from the seed, puts them in
the engine's layout (``bench/layouts/<layout>.py``, named by the
configuration), hands them to the engine, and drives it with the cell's traffic in a closed loop:
``clients`` requests per call, the next call as soon as the previous one
returned; latency runs from the call to its return.

Correctness: after the window, a sample of finished requests drawn from
the seed (the longest among them) goes through the plain reference
beside the configuration, and each served token's logit is compared
with the reference's best at its position (``logit_gap``), at the exit
the engine reports for it. The scheduler's choice of exit is not
compared with any reference.
"""
from __future__ import annotations

import gc
import os
import shutil
import sys
import time

import numpy as np

CHECK_ROWS = 8          # reference rows per compiled call
TRACE_SECONDS = 3.0     # traced part of the window in a --trace 1 run


def layout(cfg: dict):
    """The configuration's weight layout, ``bench/layouts/<layout>.py``:
    its ``arch_config(cfg)`` and ``program_params(weights, arch)``."""
    from bench import manifest
    return manifest.load_module(manifest.BENCH_DIR / "layouts"
                                / f"{cfg['layout']}.py")


class Compiles:
    """Counts JAX compile events (jax.monitoring) while ``on``: programs
    compiled or read from the compile cache, functions traced, and the
    seconds all of it took."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    # backend compiles, each with its read of the compile cache
    BACKEND = "/jax/core/compile/backend_compile_duration"
    PREFIX = "/jax/core/compile/"

    def __init__(self):
        import jax
        self.on = False
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, name, duration, **kw):
        if self.on and name.startswith(self.PREFIX):
            self.events.append((name, float(duration)))

    def seconds(self) -> float:
        return sum(d for _, d in self.events)

    def close(self) -> None:
        import jax
        self.on = False
        jax.monitoring.unregister_event_duration_listener(self._listen)

    def take(self) -> dict:
        ev, self.events = self.events, []
        return {"compiled": sum(1 for n, _ in ev if n == self.BACKEND),
                "traced": sum(1 for n, _ in ev if n == self.TRACE),
                "seconds": sum(d for _, d in ev)}


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------- the run
ALL_PAIRS = 64    # warm every reachable decode shape up to this many


class Window:
    """Drives one engine with a closed loop: ``width`` requests per call,
    the next call as soon as the previous one returned. Records every
    request with the seconds, from window start, of its call and return."""

    def __init__(self, engine, request, stream, width: int):
        self.engine, self.request = engine, request
        self.stream, self.width = stream, width
        self.served = []   # (Req, tokens, exit_layer, t_sent, t_done)
        self.t0 = time.perf_counter()

    def until(self, t_stop: float) -> None:
        """Serve until ``t_stop`` seconds into the window."""
        import jax
        while (sent := time.perf_counter() - self.t0) < t_stop:
            batch = [next(self.stream) for _ in range(self.width)]
            with jax.profiler.TraceAnnotation("bench/serve_slot"):
                assignments, info = self.engine.serve_slot(
                    [self.request(r) for r in batch], decode=True)
            done = time.perf_counter() - self.t0
            for r, (_, e), toks in zip(batch, assignments, info["texts"]):
                self.served.append((r, toks, e, sent, done))


def group_shapes(batch, assignments) -> set:
    """(group size, group length) of each exit group of one call, as
    ``serve_slot`` forms them: requests that share an exit decode
    together for the group's longest prompt plus its longest answer."""
    groups = {}
    for (p, a), (_, e) in zip(batch, assignments):
        groups.setdefault(e, []).append((p, a))
    return {(len(g), max(p for p, _ in g) + max(a for _, a in g))
            for g in groups.values()}


def warm_decode_shapes(shapes) -> None:
    """Compile, or read from the compile cache, the small programs that
    ``EdgeServingEngine._decode`` runs once per (group size, group
    length): a column of the [b, total] prompt matrix, and the stack of
    ``total`` generated [b] columns. Its per-position programs depend on
    the group size alone and are warmed by decoding each size once."""
    import jax
    import jax.numpy as jnp
    for b, total in sorted(shapes):
        mat = jnp.asarray(np.zeros((b, total), np.int32))
        col = mat[:, 0]
        jax.block_until_ready((mat[:, 1], jnp.stack([col] * total, axis=1)))


def run(ctx: dict) -> dict:
    import jax
    from repro.serve import EdgeServingEngine, Replica, Request
    from bench import traffic as gen

    cfg, mix, ref = ctx["config"], ctx["traffic"], ctx["reference"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    compiles = Compiles()
    compiles.on = True

    lay = layout(cfg)
    arch = lay.arch_config(cfg)
    vocab = arch.vocab
    weights = ref.make_weights(cfg, seed)
    params = lay.program_params(weights, arch)
    jax.block_until_ready(params)
    bs, width = cfg["batch_slots"], int(mix["clients"])
    if width > bs:
        raise ValueError(f"{width} clients > {bs} batch slots")
    lo_p, hi_p = mix["prompt_len"]
    lo_a, hi_a = mix["answer_len"]

    def request(r):     # the synchronous engine reads no request deadline
        return Request(tokens=r.prompt, deadline_s=1.0, max_new=r.answer_len)

    def sized(p, a):
        return Request(tokens=np.zeros(p, np.int32), deadline_s=1.0,
                       max_new=a)

    def engine_warmed(model: bool = True):
        """A fresh engine, its scheduler warmed through its train step.
        With ``model`` it decodes with the benchmark's weights, and each
        exit's decode step is warmed for each group size; without, it
        only schedules, and makes the same decisions."""
        engine = EdgeServingEngine(
            arch, [Replica(n, float(s)) for n, s in cfg["replicas"]],
            batch_slots=bs, cache_len=cfg["cache_len"],
            scheduler=cfg["scheduler"], seed=cfg["engine_seed"],
            init_model=model)
        for i in range(engine.agent_def.train_every + 1):
            engine.serve_slot([sized(hi_p, hi_a)] * (1 + i % width))
        if model:
            engine.params = params   # the engine's own random weights go
            for e in arch.exit_layers:
                for b in range(1, width + 1):
                    engine._decode([sized(1, 1)] * b, e)
        return engine

    # The decode loop also compiles small programs per (group size, group
    # length). Where few pairs are reachable, all are warmed. Otherwise
    # the scheduler's decisions depend on neither the requests nor the
    # decoding, so an engine that only schedules, built alike, makes the
    # window's calls ahead of it and finds the pairs it will decode.
    lengths = range(lo_p + lo_a, hi_p + hi_a + 1)
    if width * len(lengths) <= ALL_PAIRS:
        shapes = {(b, n) for b in range(1, width + 1) for n in lengths}
    else:
        planner, sizes = engine_warmed(model=False), gen.sizes(mix)
        shapes = set()
        for _ in range(int(mix["warm_slots"])):
            batch = [next(sizes) for _ in range(width)]
            assignments, _ = planner.serve_slot(
                [sized(p, a) for p, a in batch])
            shapes |= group_shapes(batch, assignments)
        del planner
    engine = engine_warmed()
    warm_decode_shapes(shapes)
    setup_compiles = compiles.take()
    setup_s = time.time() - ctx["t_process"]
    log(f"set-up {setup_s:.3f} s, {len(shapes)} group shapes, "
        f"compiles {setup_compiles}")

    # ------------------------------------------------------------ window
    win = Window(engine, request, gen.stream(mix, seed, vocab), width)
    traced = None
    if ctx["trace"]:
        tdir = ctx["trace_dir"]
        shutil.rmtree(tdir, ignore_errors=True)
        os.makedirs(tdir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
        win.t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/window"):
            win.until(min(TRACE_SECONDS, seconds))
        traced = len(win.served)
        jax.profiler.stop_trace()
    else:
        win.t0 = time.perf_counter()
    win.until(seconds)
    served = win.served
    window_s = time.perf_counter() - win.t0
    in_window = compiles.take()
    compiles.close()
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"window {window_s:.3f} s, {len(served)} requests, "
        f"compiles in window {in_window}")

    lat_ms = np.array([(d - f) * 1e3 for _, _, _, f, d in served])
    tokens = sum(len(t) for _, t, _, _, _ in served)
    failed = sum(1 for r, t, _, _, _ in served
                 if len(t) != r.answer_len
                 or not all(0 <= x < vocab for x in t))
    metrics = {
        "serve_tokens_per_s": tokens / max(d for *_, d in served),
        "serve_p95_ms": float(np.percentile(lat_ms, 95)),
        "setup_s": setup_s,
    }

    # ----------------------------------------------- after the window
    layer_ctx = dict(setup_compile_s=setup_compiles["seconds"])
    if traced is not None:
        from bench import trace as tr
        layer_ctx.update(
            events=tr.load(ctx["trace_dir"]), served=served[:traced],
            config=cfg, peaks=ctx["peaks"], module_prefix="jit_serve_step")
    del engine, win, params
    gc.collect()

    rng = np.random.default_rng(int(seed) ^ 0x5EED)
    n_check = min(int(mix["check_requests"]), len(served))
    longest = max(range(len(served)),
                  key=lambda i: len(served[i][0].prompt) + len(served[i][1]))
    rest = [i for i in range(len(served)) if i != longest]
    pick = [longest] + list(rng.choice(rest, n_check - 1, replace=False))
    gap = check_gaps(
        lambda t, e, s: ref.served_gaps(cfg, weights, t, e, s), cfg,
        [served[i] for i in pick], hi_p + hi_a - 1)
    checks = {"logit_gap": [gap, float(cfg["limits"]["logit_gap"])],
              "bad_answers": [failed, 0]}
    correct = all(v <= lim for v, lim in checks.values())
    return dict(correct=correct, attempted=len(served), failed=failed,
                metrics=metrics, memory_peak_bytes=peak, window_s=window_s,
                compiles_in_window=in_window, setup_compiles=setup_compiles,
                checks=checks, layer_ctx=layer_ctx,
                checked_tokens=sum(len(served[i][1]) for i in pick),
                checked=dict(rows=[served[i] for i in pick], weights=weights,
                             length=hi_p + hi_a - 1))


def check_rows(rows, length: int):
    """Reference inputs for served requests: tokens [B, T] (prompt, then
    each served token but the last), served [B, T] (the token served at
    each position, -1 elsewhere) and each row's exit layer."""
    b = len(rows)
    tokens = np.zeros((b, length), np.int32)
    served = np.full((b, length), -1, np.int32)
    exits = np.zeros((b,), np.int32)
    for i, (r, toks, e, *_rest) in enumerate(rows):
        seq = np.concatenate([r.prompt, np.asarray(toks[:-1], np.int32)])
        tokens[i, : len(seq)] = seq
        p = len(r.prompt)
        served[i, p - 1: p - 1 + len(toks)] = toks
        exits[i] = e
    return tokens, served, exits


def check_gaps(gaps, cfg, rows, length: int) -> float:
    """Widest gap over the served positions of ``rows``, in blocks of
    ``CHECK_ROWS``. ``gaps(tokens, exit_idx, served)`` returns the gap at
    every position: the reference's ``served_gaps``, or the control's."""
    import jax.numpy as jnp
    exits = list(cfg["exit_layers"])
    worst = 0.0
    for i in range(0, len(rows), CHECK_ROWS):
        chunk = rows[i: i + CHECK_ROWS]
        chunk = chunk + [chunk[0]] * (CHECK_ROWS - len(chunk))
        tokens, served, ex = check_rows(chunk, length)
        idx = np.array([exits.index(int(e)) for e in ex], np.int32)
        g = np.asarray(gaps(jnp.asarray(tokens), jnp.asarray(idx),
                            jnp.asarray(served)))
        worst = max(worst, float(np.max(np.where(served >= 0, g, 0.0))))
    return worst
