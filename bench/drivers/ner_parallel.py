"""The paper's parallel NER services: four per-section BiLSTM-LAN models,
one per chip, behind ``core.multimodel.MultiModelServer``. A closed loop
of one client sends CV documents one after the other (the paper's
"sequential flow of requests"): each document's sentences are routed by
section (``core.router``), padded per service to a power-of-two bucket
of rows, served by ``serve_parallel`` on the four chips at once, and
the labels are joined on the host.

The services run at the configuration's float32: matmuls at ``highest``
precision, which the configuration states. A traced run traces the
window's first ``trace_seconds`` only: every document launches thousands
of small device operations on each chip.
"""
from __future__ import annotations

import functools
import time

import jax
import numpy as np

from bench import cvcorpus, generate, weights as bench_weights
from bench.reference import bilstm_lan as ref


def _ner_step(params, ids, *, cfg):
    from repro.models import bilstm_lan
    return bilstm_lan.predict(params, cfg, ids)


def _rows(n: int, floor: int) -> int:
    return max(floor, 1 << max(n - 1, 0).bit_length())


class Server:
    """The four services, and how one document goes through them."""

    def __init__(self, cfg: dict, seed: int, devices):
        from repro.core.multimodel import ModelService, MultiModelServer
        from repro.models import bilstm_lan
        self.cfg = cfg
        self.services = cfg["services"]
        self.tok = cvcorpus.HashTokenizer(cfg["vocab_size"])
        self.params, self.lan = {}, {}
        services = []
        for i, name in enumerate(self.services):
            labels = cvcorpus.SERVICE_LABELS[name]
            lan = bilstm_lan.LANConfig(
                vocab_size=cfg["vocab_size"], n_labels=len(labels),
                d_model=cfg["d_model"], n_layers=cfg["n_layers"],
                n_heads=cfg["n_heads"],
                dtype=bench_weights.DTYPES[cfg["torch_dtype"]])
            p = bench_weights.lan(cfg, len(labels), seed, stream=i)
            bench_weights.check_layout(p, jax.eval_shape(
                functools.partial(bilstm_lan.init_params, cfg=lan),
                jax.random.key(0)))
            self.params[name], self.lan[name] = p, lan
            services.append(ModelService(
                name, functools.partial(_ner_step, cfg=lan), p))
        self.server = MultiModelServer(services, devices=devices)

    def batches(self, doc) -> tuple:
        """Route a document's sentences and pad each service's rows."""
        from repro.core import router
        sectioned: dict = {s: [] for s in router.SECTIONS}
        for sent in doc.sentences:
            sectioned[sent.section].append(sent.tokens)
        routed = router.route(sectioned)
        S = self.cfg["max_sent_len"]
        out, sents = {}, {}
        for name in self.services:
            rows = routed[name]
            ids = np.zeros((_rows(len(rows), self.cfg["row_bucket_min"]), S),
                           np.int32)
            for i, s in enumerate(rows):
                ids[i] = self.tok.pad(self.tok.encode(s), S)
            out[name], sents[name] = ids, rows
        return out, sents

    def serve(self, doc) -> tuple:
        """One document: route, serve in parallel, join. Returns (joined
        fields, the batches, the raw label ids per service)."""
        with jax.profiler.TraceAnnotation("bench.route"):
            batches, sents = self.batches(doc)
        with jax.profiler.TraceAnnotation("bench.serve_parallel"):
            out, _ = self.server.serve_parallel(batches)
        with jax.profiler.TraceAnnotation("bench.join"):
            labels = {n: np.asarray(o) for n, o in out.items()}
            fields = {}
            for name in self.services:
                names = cvcorpus.SERVICE_LABELS[name]
                S = self.cfg["max_sent_len"]
                fields[name] = [(tok, names[int(labels[name][i, j])])
                                for i, s in enumerate(sents[name])
                                for j, tok in enumerate(s[:S])
                                if names[int(labels[name][i, j])] != "O"]
        return fields, batches, labels, sents


ROWS = 4096     # reference rows per call


def check(server: Server, served: list, bf16: bool = False) -> float:
    """Widest gap, over every token of every document served, between
    the reference's best label score and the score of the label the
    service served (``bf16``: of the label the bfloat16 control puts
    first). Each service's rows go through the reference in blocks."""
    worst = -np.inf
    S = server.cfg["max_sent_len"]
    for name in server.services:
        ids = np.concatenate([b[name] for b, _, _ in served])
        labels = np.concatenate([lab[name] for _, lab, _ in served])
        mask = np.zeros(ids.shape, bool)
        row = 0
        for b, _, sents in served:
            for i, sent in enumerate(sents[name]):
                mask[row + i, :min(len(sent), S)] = True
            row += b[name].shape[0]
        pad = -len(ids) % ROWS
        ids, labels, mask = (np.pad(a, ((0, pad), (0, 0)))
                             for a in (ids, labels, mask))
        p = server.params[name]
        for k in range(0, len(ids), ROWS):
            blk = ids[k:k + ROWS]
            s = ref.scores(p, blk, n_heads=server.cfg["n_heads"])
            lab = labels[k:k + ROWS]
            if bf16:
                lab = np.asarray(np.argmax(ref.scores(
                    p, blk, n_heads=server.cfg["n_heads"], bf16=True),
                    axis=-1))
            worst = max(worst, ref.widest_gap(s, lab, mask[k:k + ROWS]))
    return float(worst)


def serve(ctx) -> tuple:
    """Services on their chips, lead-in, the window. Returns (server,
    record, what each document in the window was served)."""
    cfg, mix = ctx.config, ctx.mix
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    server = Server(cfg, ctx.seed, ctx.devices)
    ctx.phase("weights")
    docs = generate.doc_stream(mix, ctx.seed)
    # the one shape of each service, on its chip, then steady traffic
    t_lead = time.perf_counter()
    while time.perf_counter() < t_lead + mix["lead_in_s"]:
        server.serve(next(docs))
    ctx.phase("warm-up and lead-in")
    times, kept = [], []
    t_open = ctx.open_window()
    while time.perf_counter() < t_open + ctx.seconds:
        if time.perf_counter() >= t_open + cfg["trace_seconds"]:
            ctx.stop_trace()
        doc = next(docs)
        t0 = time.perf_counter()
        _, batches, labels, sents = server.serve(doc)
        times.append((t0, time.perf_counter()))
        kept.append((batches, labels, sents))
    ctx.close_window()
    ctx.read_memory()
    record = {"kind": "ner", "docs": times, "attempted": len(times),
              "failed": 0}
    return server, record, kept


def run(ctx) -> dict:
    server, record, kept = serve(ctx)
    t0 = time.perf_counter()
    gap = check(server, kept)
    ctx.note(f"reference over all {len(kept)} documents, "
             f"{time.perf_counter() - t0:.1f} s")
    record["checks"] = {"max_label_gap": {
        "value": gap, "limit": ctx.config["check"]["max_label_gap"]}}
    return record


def readings(ctx, control: bool) -> dict:
    """The program's compared number and, with ``control``, the bfloat16
    control's on the same documents (bench/control.py)."""
    server, record, kept = serve(ctx)
    out = {"max_label_gap": check(server, kept), "docs": len(kept),
           "attempted": record["attempted"],
           "window_compiles": ctx.window_compiles}
    if control:
        out["control_gap"] = check(server, kept, bf16=True)
    return out
