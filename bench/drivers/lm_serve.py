"""A language-model cell: the serving engine as ``launch/serve.py``
builds it (paged KV pool sized from the compiled programs, the Pallas
paged-attention kernel), behind ``Scheduler`` and ``AsyncServeLoop``,
pumped inline: submit what is due, then ``run_once``.

Set-up: seeded weights on the device, the engine, then every program
shape this cell's traffic can reach, run once through the engine's own
entry points, then a lead-in of the cell's own traffic. The window
follows; after it, requests due in it finish their first token (for the
time to first token), the peak memory is read, the engine is freed, and
the plain reference checks a seeded sample of greedy requests.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import numpy as np

from bench import generate, weights as bench_weights
from bench.reference import dense_decoder
from bench.work import Dense, Work

# after the window: how long requests due in it may take to show their
# first token before they count as never served
DRAIN_S = 60.0


@dataclasses.dataclass
class Req:
    """The harness's own record of one request."""
    spec: generate.RequestSpec
    due: float
    handle: object = None
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    fed: int = 0              # prompt tokens resident, as the harness counts
    admitted: bool = False
    done: bool = False
    error: str | None = None

    @property
    def prompt_len(self) -> int:
        return len(self.spec.prompt)


def program_config(cfg: dict):
    """The program's ArchConfig for a configuration file: its registered
    architecture with every size the file states applied."""
    from repro.configs.base import get_config
    base = get_config(cfg["program_arch"])
    return dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or
        cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        qk_norm=cfg["architectures"][0] in dense_decoder.QK_NORM_ARCHS,
        dtype=bench_weights.DTYPES[cfg["torch_dtype"]],
        source=cfg["source"])


def _bucket(n: int, cap: int) -> int:
    """The engine's power-of-two width bucket (8, 16, ...), capped."""
    b = 8
    while b < n:
        b *= 2
    return min(b, cap)


def warm_shapes(prompts, chunk: int, batch: int, max_seq: int) -> dict:
    """Every program shape the prompt lengths can reach: admission
    widths with the most rows that can share them, and chunk-window
    widths."""
    first: dict = {}
    windows = set()
    for P in prompts:
        W = _bucket(min(P, chunk), max_seq)
        first[W] = first.get(W, 0) + 1
        rem = P - chunk
        while rem > 0:
            c = min(rem, chunk)
            if c > 1:
                windows.add(_bucket(c, max_seq))
            rem -= c
    return {"admit": {W: min(n, batch) for W, n in first.items()},
            "chunk": sorted(windows)}


def warm_up(eng, shapes: dict, chunk: int, vocab: int) -> int:
    """Run each shape once through the engine's own entry points with
    throw-away requests, leaving the engine empty. Returns programs run."""
    from repro.serve.engine import Request
    rng = np.random.default_rng(0)
    rid = iter(range(-1, -10**6, -1))
    runs = 0

    def req(P, n_new):
        return Request(rid=next(rid), max_new_tokens=n_new,
                       prompt=rng.integers(0, vocab, P).tolist())

    for W, n_max in sorted(shapes["admit"].items()):
        for n in range(1, n_max + 1):
            assert eng.add_requests([req(W, 1) for _ in range(n)]) == n
            eng.step()                       # collects the finished
            runs += 1
    W0 = max(shapes["admit"])
    assert eng.add_requests([req(W0, 2)]) == 1
    eng.step()                               # the decode program
    runs += 1
    for W in shapes["chunk"]:
        assert eng.add_requests([req(chunk + W, 1)]) == 1
        eng.step()                           # a chunk window of width W
        runs += 1
    assert eng.active == 0 and eng.pool.available == eng.pool.total
    return runs


class Pump:
    """Submits the cell's traffic and pumps the loop; keeps the records
    the metrics are read from."""

    def __init__(self, loop, mix: dict, seed: int, vocab: int, chunk: int,
                 dense: Dense):
        self.loop, self.eng = loop, loop.engine
        self.mix = mix
        self.stream = generate.lm_stream(mix, seed, vocab)
        self.chunk = chunk
        self.dense = dense
        self.reqs: list = []
        self.live: list = []              # admitted or queued, unfinished
        self.next_due = None
        self.next_spec = None
        self.closed_free = 0              # closed loop: clients idle
        self.work = Work()
        self.count_work = False
        self.work_mismatch = 0
        self.loop_time = 0.0
        self.late: list = []              # submit - due, open loop

    # ------------------------------------------------------------ traffic
    def start(self, t0: float) -> None:
        if self.mix["loop"] == "closed":
            self.closed_free = self.mix["clients"]
        else:
            self.next_spec, gap = next(self.stream)
            self.next_due = t0 + gap

    def _submit(self, spec, due: float) -> None:
        from repro.serve.engine import Request
        from repro.serve.sampling import SamplingParams
        r = Req(spec=spec, due=due)
        samp = SamplingParams() if spec.greedy else SamplingParams(
            temperature=spec.temperature, top_k=spec.top_k,
            seed=spec.sample_seed)
        request = Request(rid=spec.index, prompt=spec.prompt,
                          max_new_tokens=spec.max_new, sampling=samp)

        def on_token(tok, lp, r=r):
            r.times.append(time.perf_counter())
            r.tokens.append(tok)

        with jax.profiler.TraceAnnotation("bench.submit"):
            r.handle = self.loop.submit(request, on_token)
        self.reqs.append(r)
        self.live.append(r)

    def submit_due(self, now: float) -> None:
        if self.mix["loop"] == "closed":
            while self.closed_free:
                spec, _ = next(self.stream)
                self._submit(spec, now)
                self.closed_free -= 1
            return
        while self.next_due <= now:
            self._submit(self.next_spec, self.next_due)
            self.late.append(time.perf_counter() - self.next_due)
            self.next_spec, gap = next(self.stream)
            self.next_due += gap

    # ------------------------------------------------------------ pumping
    def tick(self) -> bool:
        """Submit what is due, then one ``run_once``; account its work.
        Sleeps until the next arrival when there is nothing to do."""
        now = time.perf_counter()
        self.submit_due(now)
        m0 = dict(self.eng.metrics)
        before = {id(r): len(r.tokens) for r in self.live}
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.run_once"):
            busy = self.loop.run_once()
        t1 = time.perf_counter()
        if not busy:
            if self.next_due is not None:
                time.sleep(max(0.0, min(self.next_due - t1, 0.05)))
            return False
        self.loop_time += t1 - t0
        self._account(m0, before)
        return True

    def _account(self, m0: dict, before: dict) -> None:
        """The work of the tick just run, from the harness's own records:
        each request's prompt length and the tokens it streamed."""
        m = self.eng.metrics
        kind = "chunk" if m["chunk_steps"] > m0["chunk_steps"] else "decode"
        d, chunk = self.dense, self.chunk
        fed_prompt = 0
        done = []
        for r in self.live:
            n0, n1 = before.get(id(r), 0), len(r.tokens)
            P = r.prompt_len
            if not r.admitted:
                req = r.handle.request
                if req.admitted_s is None:
                    continue              # still queued
                r.admitted = True
                r.fed = min(P, chunk)
                if self.count_work:
                    self.work["admit"].add_row(
                        d, list(range(1, r.fed + 1)), int(r.fed == P),
                        kernel=False)
                if r.fed == P:
                    n0 += 1               # token 1 came from admission
            if r.fed < P:
                c = min(P - r.fed, chunk)
                ctxs = list(range(r.fed + 1, r.fed + c + 1))
                r.fed += c
                fed_prompt += c
                if self.count_work:
                    self.work[kind].add_row(d, ctxs, int(r.fed == P))
            elif n1 > n0:
                ctx = P + n0              # input: token n0 at position P+n0-1
                if self.count_work:
                    self.work[kind].add_row(d, [ctx], 1)
            if r.handle.done:
                done.append(r)
        if self.count_work:
            self.work[kind].calls += 1
        if kind == "chunk" and fed_prompt != \
                m["chunk_prefill_tokens"] - m0["chunk_prefill_tokens"]:
            self.work_mismatch += 1
        for r in done:
            self.live.remove(r)
            if r.handle.error is not None:
                r.error = str(r.handle.error)
            if self.mix["loop"] == "closed":
                self.closed_free += 1


def make_engine(ctx, params):
    """The engine as the launcher builds it, every program shape of the
    cell's traffic warmed up."""
    from repro.launch.serve import build_engine
    from repro.models.model import build_model
    cfg, eng_shape = ctx.config, ctx.config["engine"]
    model = build_model(program_config(cfg))
    bench_weights.check_layout(params,
                               jax.eval_shape(model.init, jax.random.key(0)))
    eng, _, _ = build_engine(model, params, batch=eng_shape["batch"],
                             max_seq=eng_shape["max_seq"],
                             prefill_chunk=eng_shape.get("prefill_chunk"))
    ctx.note(f"engine: batch {eng.B}, max_seq {eng.max_seq}, block "
             f"{eng.block_size}, chunk {eng.prefill_chunk}, pool "
             f"{eng.pool.total} blocks, kernel {eng.use_kernel}")
    ctx.phase("engine")
    prompts, _, _ = generate.sizes(ctx.mix)
    shapes = warm_shapes(prompts, eng.prefill_chunk, eng.B, eng.max_seq)
    n = warm_up(eng, shapes, eng.prefill_chunk, cfg["vocab_size"])
    ctx.note(f"warm-up: {n} programs run, shapes {shapes}")
    ctx.phase("warm-up")
    return eng


def serve(ctx, eng) -> dict:
    """Lead-in, the window, and the drain for the first tokens of the
    requests due in it; leaves the engine empty. Returns the record the
    metrics read (no checks yet)."""
    from repro.serve.async_loop import AsyncServeLoop
    from repro.serve.scheduler import Scheduler
    cfg, mix = ctx.config, ctx.mix
    loop = AsyncServeLoop(Scheduler(eng), name=cfg["name"])
    dense = Dense.from_config(cfg)
    pump = Pump(loop, mix, ctx.seed, cfg["vocab_size"], eng.prefill_chunk,
                dense)
    t_lead = time.perf_counter()
    pump.start(t_lead)
    while time.perf_counter() < t_lead + mix["lead_in_s"]:
        pump.tick()
    ctx.phase("lead-in")
    m0, s0 = dict(eng.metrics), len(loop.scheduler.stats.queue_wait_s)
    lm0 = dict(loop.metrics)
    loop_time0 = pump.loop_time
    pump.count_work = True
    q_open = len(loop.scheduler.queue)
    t_open = ctx.open_window()
    while time.perf_counter() < t_open + ctx.seconds:
        pump.tick()
    t_close = ctx.close_window()
    q_close = len(loop.scheduler.queue)
    pump.count_work = False
    lm1, m1 = dict(loop.metrics), dict(eng.metrics)
    waits = loop.scheduler.stats.queue_wait_s[s0:]
    loop_time = pump.loop_time - loop_time0
    # open loop: requests due in the window show their first token,
    # arrivals going on meanwhile (a closed loop reports no such time)
    due = [r for r in pump.reqs if t_open <= r.due < t_close]
    t_end = time.perf_counter() + DRAIN_S
    while mix["loop"] == "poisson" and time.perf_counter() < t_end and \
            any(not r.times and r.error is None for r in due):
        pump.tick()
    drain_end = time.perf_counter()
    ctx.read_memory()
    for r in pump.reqs:                   # drop every path to the engine
        r.done, r.handle = r.handle.done, None
    # every request the window had to serve: sent before it closed, not
    # finished before it opened
    served = [r for r in pump.reqs if r.due < t_close and
              not (r.done and r.times and r.times[-1] < t_open)]
    loop.abort()
    record = {
        "kind": "lm", "traffic_loop": mix["loop"], "drain_end": drain_end,
        "window": (t_open, t_close), "reqs": pump.reqs,
        "due": due, "work": pump.work, "dense": dense,
        "loop": {k: lm1[k] - lm0[k] for k in lm0}, "loop_time_s": loop_time,
        "engine": {k: m1[k] - m0[k] for k in m0}, "queue_waits_s": waits,
        "late_s": pump.late, "work_mismatch": pump.work_mismatch,
        "queue_open": q_open, "queue_close": q_close,
        "attempted": len(served),
        "failed": sum(r.error is not None for r in served),
    }
    if pump.late:
        from bench.stats import percentile
        ctx.note(f"arrivals submitted late by {1e3 * max(pump.late):.2f} ms "
                 f"at most, {1e3 * percentile(pump.late, 0.99):.2f} ms p99")
    ctx.note(f"window: {record['loop']['ticks']} ticks, "
             f"{record['engine']['chunk_steps']} chunk steps, "
             f"{record['engine']['preemptions']} preemptions, work "
             f"mismatches {pump.work_mismatch}")
    return record


def check(record: dict, params, cfg: dict, seed: int, *,
          control: bool = False) -> dict:
    """The reference's widest logit gap over a seeded sample of greedy
    requests (``control``: also the fp8 control's, on the same sample)."""
    sample = _sample(record["reqs"], seed, cfg["check"])
    out = {"requests": len(sample),
           "tokens": sum(len(r.tokens) for r in sample)}
    gaps = [dense_decoder.served_gap(params, cfg, r.spec.prompt, r.tokens)
            for r in sample]
    out["max_logit_gap"] = max(gaps) if gaps else float("inf")
    if control:
        out["control_gap"] = max(
            dense_decoder.control_gap(params, cfg, r.spec.prompt, r.tokens)
            for r in sample)
    return out


def run(ctx) -> dict:
    """One run of an LM cell; returns the record the metrics read."""
    cfg = ctx.config
    params = bench_weights.dense(cfg, ctx.seed)
    ctx.phase("weights")
    eng = make_engine(ctx, params)
    record = serve(ctx, eng)
    del eng
    gc.collect()
    t0 = time.perf_counter()
    got = check(record, params, cfg, ctx.seed)
    ctx.note(f"reference over {got['requests']} greedy requests, "
             f"{got['tokens']} served tokens, "
             f"{time.perf_counter() - t0:.1f} s")
    record["checks"] = {"max_logit_gap": {
        "value": got["max_logit_gap"],
        "limit": cfg["check"]["max_logit_gap"]}}
    return record


def _sample(reqs: list, seed: int, check: dict) -> list:
    """Greedy requests for the reference, drawn from the seed: the longest
    finished one, then the other finished ones in a seeded order, then
    those still streaming (longest first), until ``min_tokens`` served
    tokens or ``max_requests`` requests."""
    greedy = [r for r in reqs if r.spec.greedy and r.error is None
              and r.tokens]
    done = sorted((r for r in greedy if r.done), key=lambda r: -len(r.tokens))
    rest = sorted((r for r in greedy if not r.done),
                  key=lambda r: -len(r.tokens))
    order = done[:1] + [done[1:][i] for i in
                        np.random.default_rng(seed).permutation(
                            max(len(done) - 1, 0))] + rest
    out, tokens = [], 0
    for r in order:
        if tokens >= check["min_tokens"] or len(out) >= check["max_requests"]:
            break
        out.append(r)
        tokens += len(r.tokens)
    return out
