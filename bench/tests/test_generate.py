"""The traffic generator: every seed does the same work in another
order, and a seed reproduces its run."""
import itertools

from bench import generate, spec

BIG = 2**31 + 12345


def take(mix, seed, n, vocab=1000):
    return list(itertools.islice(generate.lm_stream(mix, seed, vocab), n))


def test_same_sizes_for_every_seed_in_another_order():
    mix = spec.traffic("decode-sat")
    a, b = take(mix, BIG, mix["pool"]), take(mix, BIG + 1, mix["pool"])
    key = lambda x: sorted((len(s.prompt), s.max_new, s.greedy) for s, _ in x)
    assert key(a) == key(b)
    assert [len(s.prompt) for s, _ in a] != [len(s.prompt) for s, _ in b]
    # the fixed head: the same sizes in the same order for every seed
    h = mix["fixed_head"]
    assert [(len(s.prompt), s.max_new) for s, _ in a[:h]] == \
        [(len(s.prompt), s.max_new) for s, _ in b[:h]]


def test_a_fixed_head_fixes_sizes_and_arrivals():
    mix = spec.traffic("extract-rate.deepseek-7b")
    a, b = take(mix, BIG, 50), take(mix, BIG + 1, 50)
    assert [(len(s.prompt), s.max_new, g) for s, g in a] == \
        [(len(s.prompt), s.max_new, g) for s, g in b]
    assert [s.prompt for s, _ in a] != [s.prompt for s, _ in b]


def test_a_seed_reproduces_its_requests():
    mix = spec.traffic("extract-rate.deepseek-7b")
    a, b = take(mix, BIG, 20), take(mix, BIG, 20)
    assert [(s.prompt, s.max_new, s.sample_seed, g) for s, g in a] == \
        [(s.prompt, s.max_new, s.sample_seed, g) for s, g in b]


def test_sizes_respect_the_mix():
    for name in ("decode-sat", "extract-rate.deepseek-7b"):
        mix = spec.traffic(name)
        prompts, outputs, gaps = generate.sizes(mix)
        assert prompts.min() >= mix["prompt"]["min"]
        assert prompts.max() <= mix["prompt"]["max"]
        assert outputs.min() >= mix["output"]["min"]
        assert outputs.max() <= mix["output"]["max"]
        reqs = take(mix, BIG, mix["pool"])
        assert sum(s.greedy for s, _ in reqs) == mix["pool"] // 2
        assert all(s.top_k == 50 for s, _ in reqs if not s.greedy)


def test_documents_are_one_corpus_in_the_seed_order():
    mix = spec.traffic("cv-docs-seq")
    a = list(itertools.islice(generate.doc_stream(mix, BIG), mix["pool"]))
    b = list(itertools.islice(generate.doc_stream(mix, BIG + 1), mix["pool"]))
    text = lambda docs: sorted(d.text for d in docs)
    assert text(a) == text(b) and [d.text for d in a] != [d.text for d in b]
