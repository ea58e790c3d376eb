"""The sampler runs inside the decode program and does only what the
batch's sampling parameters ask for (PR 33): for the same key its tokens
are those of the formula every step computed whole before (kept here as
the plain reference), the sort and the draw sit inside conditionals of
batch-level predicates, and a decode step is one dispatch. CPU, debug
widths: tokens, jaxprs and counts, no timing."""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.llm import ContinuousBatchingEngine, SamplingParams
from ray_tpu.models import LlamaConfig, LlamaModel, MoEConfig, model_for

B, V = 6, 512


def reference_sample(logits, temps, top_ks, key):
    """PR 32's ``_sample_impl`` (16338d3), on the key ITS caller split
    off on the host: everything for every row, then the ``where``."""
    key, sub = jax.random.split(key)
    n, vocab = logits.shape
    keys = jax.random.split(sub, n)
    greedy = jnp.argmax(logits, axis=-1)

    def sample_row(lg, temp, tk, k):
        scaled = lg / jnp.maximum(temp, 1e-6)

        def apply_topk(s):
            kth = jnp.sort(s)[vocab - jnp.maximum(tk, 1)]
            return jnp.where(s >= kth, s, -1e30)
        scaled = jax.lax.cond(tk > 0, apply_topk, lambda s: s, scaled)
        return jax.random.categorical(k, scaled)

    sampled = jax.vmap(sample_row)(logits, temps, top_ks, keys)
    return jnp.where(temps <= 0.0, greedy, sampled), key


def dense():
    model = LlamaModel(LlamaConfig.debug(vocab_size=V, max_seq_len=128))
    return model, model.init(jax.random.key(0))


def expert():
    model = model_for(MoEConfig.debug_olmoe(vocab_size=V))
    return model, jax.jit(model.init)(jax.random.key(1))


@pytest.fixture(scope="module", params=[dense, expert])
def built(request):
    return request.param()


@pytest.fixture(scope="module")
def tiny_model():
    return dense()


def make_engine(built, **kw):
    model, params = built
    kw = {"max_slots": 4, "max_seq": 128, "prefill_buckets": (16, 64),
          "block_size": 8, **kw}
    return ContinuousBatchingEngine(model, params, **kw)


def prompt(n, start):
    return [(start + 7 * i) % 500 + 1 for i in range(n)]


# -- (a) the same tokens as the formula that computed everything ------------
def logits_of(seed, tied=False):
    lg = jax.random.normal(jax.random.key(seed), (B, V)) * 3.0
    if tied:                    # few distinct values: ties at every rank
        lg = jnp.round(lg)
    return lg


MIXES = {
    "all_greedy": ([0.0] * B, [0] * B),
    "all_temperature": ([0.7, 1.0, 2.0, 0.3, 1.5, 1.0], [0] * B),
    "temperature_and_top_k": ([0.7, 1.0, 2.0, 0.3, 1.5, 1.0],
                              [1, 50, V, 5, 2, 200]),
    "greedy_and_sampled_rows": ([0.0, 1.0, 0.0, 2.0, 0.0, 0.5],
                                [0, 0, 50, 50, 1, 0]),
    "top_k_on_greedy_rows_only": ([0.0] * B, [1, 50, V, 0, 3, 7]),
    "one_sampled_row": ([0.0, 0.0, 0.0, 0.0, 0.0, 1.3], [0] * B),
    "one_top_k_row": ([1.0] * B, [0, 0, 40, 0, 0, 0]),
    "top_k_1": ([1.0] * B, [1] * B),
    "top_k_50": ([1.0] * B, [50] * B),
    "top_k_V": ([1.0] * B, [V] * B),
}


@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied"])
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_tokens_are_the_whole_formulas_for_the_same_key(tiny_model, mix, tied):
    eng = make_engine(tiny_model)
    temps, top_ks = (jnp.asarray(MIXES[mix][0], jnp.float32),
                     jnp.asarray(MIXES[mix][1], jnp.int32))
    want_fn = jax.jit(reference_sample)
    for seed in range(3):
        logits, key = logits_of(seed, tied), jax.random.key(100 + seed)
        got, got_key = eng._sample(logits, temps, top_ks, key)
        want, want_key = want_fn(logits, temps, top_ks, key)
        assert got.dtype == jnp.int32 and got.shape == (B,)
        assert got.tolist() == want.tolist()
        sampled = bool((temps > 0).any())
        # the key moves only when something was drawn with it
        assert (jax.random.key_data(got_key).tolist()
                == jax.random.key_data(want_key if sampled else key).tolist())
        if mix == "top_k_1" and not tied:       # only the largest is left
            assert got.tolist() == jnp.argmax(logits, -1).tolist()


def test_top_k_past_the_vocabulary_keeps_every_token(tiny_model):
    """``top_k`` comes from a request: one larger than V masks nothing
    (the index of the k-th largest would wrap past the smallest)."""
    eng = make_engine(tiny_model)
    logits, key = logits_of(7), jax.random.key(7)
    temps = jnp.full((B,), 1.5, jnp.float32)
    none, _ = eng._sample(logits, temps, jnp.zeros(B, jnp.int32), key)
    past, _ = eng._sample(logits, temps, jnp.full(B, V + 9, jnp.int32), key)
    assert past.tolist() == none.tolist()


# -- (b) what the decode program holds ---------------------------------------
def decode_args(eng):
    i32 = jnp.int32
    n, nb = eng.max_slots, eng.blocks_per_slot
    return [eng.params, jnp.zeros(n, i32), eng.kv, jnp.zeros((n, nb), i32),
            jnp.zeros(n, i32), jnp.zeros(n, jnp.float32), jnp.zeros(n, i32),
            jax.random.key(0),
            None if eng._ffn_counts is None else eng._ffn_counts[0]]


def walk(jaxpr, in_cond=False):
    """(primitive, inside a ``cond`` branch, operand shapes) of every
    equation, through every nested jaxpr."""
    for eqn in jaxpr.eqns:
        yield (eqn.primitive.name, in_cond,
               [getattr(v.aval, "shape", ()) for v in eqn.invars])
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else [val]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from walk(
                        inner, in_cond or eqn.primitive.name == "cond")


def test_sort_and_draw_sit_inside_conditionals_and_no_logits_leave(built):
    eng = make_engine(built)
    traced = eng._decode.trace(*decode_args(eng))
    eqns = list(walk(traced.jaxpr.jaxpr))
    over_vocab = [(name, inside) for name, inside, shapes in eqns
                  if name in ("sort", "top_k", "approx_top_k")
                  and any(s[-1:] == (V,) for s in shapes)]
    random = [(name, inside) for name, inside, _ in eqns
              if name.startswith(("random_", "threefry"))]
    # the witness sees them: one sort over the vocabulary, a key split
    # and the draw's bits
    assert ("sort", True) in over_vocab
    assert {"random_split", "random_bits"} <= {name for name, _ in random}
    assert all(inside for _, inside in over_vocab + random), (over_vocab,
                                                              random)
    # predicates of the whole batch: the conditionals are scalar ones,
    # outside any vmap (a batched predicate would lower to a select)
    conds = [shapes[0] for name, _, shapes in eqns if name == "cond"]
    assert len(conds) >= 2 and all(shape == () for shape in conds)
    # the program's outputs: tokens, pool, key (and the expert load)
    n = eng.max_slots
    outs = jax.tree.leaves(traced.out_info)
    assert all(tuple(o.shape) != (n, V) for o in outs)
    assert tuple(outs[0].shape) == (n,) and outs[0].dtype == jnp.int32


# -- (c) one dispatch a decode step, one compiled program --------------------
class Counting:
    def __init__(self, fn, log, name):
        self.fn, self.log, self.name = fn, log, name

    def __call__(self, *args):
        self.log.append(self.name)
        return self.fn(*args)


def run(eng, reqs):
    while eng.has_work():
        eng.step()
    return [r.output for r in reqs]


def test_a_decode_step_is_one_dispatch_of_one_program(built, monkeypatch):
    eng = make_engine(built, max_slots=2)
    log = []
    decode = eng._decode
    for name in ("_decode", "_sample", "_prefill", "_prefill_prefix",
                 "_insert", "_gather"):
        setattr(eng, name, Counting(getattr(eng, name), log, name))
    # what the host dispatched beside its programs before: the key split
    # (now only where a program that samples is traced, at an admission)
    monkeypatch.setattr(jax.random, "split", Counting(
        jax.random.split, log, "jax.random.split"))
    reqs = [eng.submit(prompt(9, 0), SamplingParams(max_tokens=30)),
            eng.submit(prompt(9, 40), SamplingParams(
                max_tokens=12, temperature=0.8, top_k=20))]
    eng.step()                  # both admitted and their first tokens drawn
    assert eng.slots.count(None) == 0 and "_sample" in log
    ahead = 0
    while eng.has_work():
        if len(reqs) == 2 and reqs[1].done.is_set():
            # the sampled request left: a greedy one beside the first,
            # with a stop token (never sampled), which holds no step
            # ahead back: a stop is found as its step is read
            reqs.append(eng.submit(prompt(9, 80), SamplingParams(
                max_tokens=5, stop_token_ids=(9999,))))
            eng.step()
            continue
        before = len(log)
        eng.step()              # a step with no admission
        ahead += eng._in_flight is not None
        assert set(log[before:]) <= {"_decode"}, log[before:]
    assert ahead > 0
    assert [len(r.output) for r in reqs] == [30, 12, 5]
    # every decode step was ONE call, of one compiled program, whether
    # greedy, sampled with top-k, dispatched ahead or not
    assert log.count("_decode") == eng.stats["decode_steps"] == 29
    assert decode._cache_size() == 1


# -- (d) the counters ----------------------------------------------------------
def test_counters_say_how_often_the_branches_engaged(tiny_model):
    eng = make_engine(tiny_model, max_slots=2)
    eng.generate([prompt(9, 0), prompt(9, 40)], SamplingParams(max_tokens=6))
    assert eng.stats["decode_steps"] == 5
    assert eng.stats["decode_steps_sampled"] == 0
    assert eng.stats["decode_steps_topk"] == 0
    # a sampled request of 4 tokens (3 decode steps) beside a greedy one
    # of 9: the batch holds a sampled row for 3 of its 8 steps, and the
    # step dispatched ahead as that request ended drew for its row too
    # (the row is dropped as it is read: ``decode_rows_dropped``)
    eng = make_engine(tiny_model, max_slots=2)
    eng.generate([prompt(9, 0)], SamplingParams(max_tokens=1))   # no decode
    a = eng.submit(prompt(9, 80), SamplingParams(max_tokens=9))
    b = eng.submit(prompt(9, 120), SamplingParams(max_tokens=4,
                                                  temperature=0.9))
    run(eng, [a, b])
    assert (len(a.output), len(b.output)) == (9, 4)
    assert eng.stats["decode_steps"] == 8
    assert eng.stats["decode_steps_sampled"] == 3 + 1
    assert eng.stats["decode_rows_dropped"] == 1
    assert eng.stats["decode_steps_topk"] == 0
    # top-k on a GREEDY row is counted as asked (the program's sort
    # stands behind the draw's conditional and does not run)
    eng = make_engine(tiny_model, max_slots=2)
    eng.generate([prompt(9, 0)], SamplingParams(max_tokens=6, top_k=5))
    assert eng.stats["decode_steps"] == 5
    assert eng.stats["decode_steps_sampled"] == 0
    assert eng.stats["decode_steps_topk"] == 5


# -- (e) determinism -----------------------------------------------------------
def test_two_engines_stream_the_same_sampled_tokens(built):
    sampling = [SamplingParams(max_tokens=10, temperature=1.0),
                SamplingParams(max_tokens=14, temperature=0.7, top_k=40),
                SamplingParams(max_tokens=12),
                SamplingParams(max_tokens=8, temperature=1.5, top_k=1)]
    prompts = [prompt(9 + k, 30 * k) for k in range(4)]
    outs = []
    for _ in range(2):
        eng = make_engine(built)
        reqs = [eng.submit(p, s) for p, s in zip(prompts, sampling)]
        run(eng, reqs)
        streamed = []
        for r in reqs:
            streamed.append(list(r.iter_tokens()))
            assert streamed[-1] == r.output
        outs.append(streamed)
    assert outs[0] == outs[1]
    assert [len(t) for t in outs[0]] == [10, 14, 12, 8]
    # the greedy row beside sampled ones is the greedy row alone
    alone = make_engine(built).generate([prompts[2]], sampling[2])[0]
    assert alone.output == outs[0][2]
    # and a second draw of the same engine differs: the key moved on
    again = eng.generate([prompts[0]], sampling[0])[0]
    assert again.output != outs[0][0]
