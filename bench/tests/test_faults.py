"""A run with its timed path broken underneath must come out not
correct. Each test drives the whole of ``bench/run.py`` but the look for
a chip (the CPU rehearsal sizes of ``bench/rehearse.py``), with one
fault planted in the program, and sees ``correct`` false; the same run
unbroken comes out correct. The cells have no exchange between chips,
so that fault has no test."""
from bench import rehearse, run as bench_run, spec

SEED = 2**31 + 11


# the four-chip NER cell, rehearsed here though BENCHMARK.json does not
# hold it yet (PERF.md, section 7)
NER = {"name": "cv-ner4.docs-seq", "config": "cv-ner4",
       "traffic": "cv-docs-seq", "chips": 4}


def execute(workload, seconds=3.0):
    cell = NER if workload == NER["name"] else spec.cell(workload)
    args = bench_run.parse(["--workload", workload, "--seed", str(SEED),
                            "--seconds", str(seconds)])
    result, _ = bench_run.execute(
        args, rehearsal=True,
        config_override=rehearse.reduced(spec.config(cell["config"])),
        mix_override=rehearse.rehearse_mix(spec.traffic(cell["traffic"])),
        cell_override=cell)
    return result


def test_sound_lm_run_is_correct():
    assert execute("qwen3-4b.decode-sat")["correct"]


def test_decode_step_returning_its_state_unchanged(monkeypatch):
    """The decode program hands back the KV pool it was given: tokens
    written by decode steps are never stored."""
    from repro.serve import engine
    real = engine.paged_decode_step

    def stale(model, plan, kernel, p, tok, caches, *a):
        nxt, logp, _ = real(model, plan, kernel, p, tok, caches, *a)
        return nxt, logp, caches

    monkeypatch.setattr(engine, "paged_decode_step", stale)
    assert not execute("qwen3-4b.decode-sat")["correct"]


def test_half_the_batch_left_out(monkeypatch):
    """The decode program computes the first half of its rows and gives
    the rest the first row's logits."""
    from repro.serve import engine
    real = engine.paged_decode_step

    def half(model, plan, kernel, p, tok, caches, lengths, table, temps,
             top_ks, seeds, ctrs):
        B = tok.shape[0]
        logits, caches = model.decode_step(p, tok, caches, lengths, plan,
                                           block_table=table,
                                           paged_kernel=kernel)
        logits = logits.at[B // 2:].set(logits[0])
        from repro.serve import sampling
        nxt, logp = sampling.sample(logits[:, -1, :], temps, top_ks, seeds,
                                    ctrs)
        return nxt, logp, caches

    monkeypatch.setattr(engine, "paged_decode_step", half)
    assert not execute("qwen3-4b.decode-sat")["correct"]


def test_token_altered_where_produced(monkeypatch):
    """The engine commits the decode program's token plus one."""
    from repro.serve.engine import ServingEngine
    real = ServingEngine._commit_decode

    def altered(self, active, finished, nxt, logp):
        return real(self, active, finished, nxt + 1, logp)

    monkeypatch.setattr(ServingEngine, "_commit_decode", altered)
    assert not execute("deepseek-7b.extract-rate")["correct"]


def test_sound_ner_run_is_correct():
    assert execute("cv-ner4.docs-seq", seconds=1.0)["correct"]
