"""The one harness of the model-serving test files (``tests/test_*_serving.py``
and ``tests/test_*_engine.py``): the helpers and the cases they share,
written once. pytest does not collect this module; a family's file
describes its family (``Family``), takes the cases its description asks
for (``globals().update(cases_of(FAMILY))``: a case is collected in, and
counts for, the file that instantiates it) and keeps the tests that are
its own. ``docs/serving.md``, "Adding a model family's tests", says what
a new file gives and gets.

Why a family is a file: the tier-1 sweep runs ``-n 6 --dist loadfile``, a
file is one worker's, so everything below that is cached (``make``,
``jitted``, ``honest``, ``shared_engine``) is built once a family a run.
What is cached is shared, so it is read-only or counted by difference: an
engine that several cases use is never asserted on by absolute counters,
a test that edits ``params`` copies them first, and one that patches what
a program traces takes a model of its own (``fresh``).
"""

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import ContinuousBatchingEngine, SamplingParams
from ray_tpu.models import model_for

I32 = jnp.int32


def drawn(norms=(), router_width=None, stacks=("layers",), also=None):
    """-> ``seeded(model, seed)``: ``init``'s weights with the ``norms`` of
    ``stacks`` drawn about what ``init`` gives them (a scale's 1, a
    LayerNorm bias's 0: a program that drops one differs), ``also(layers,
    key) -> key`` drawing what else the family has, and the router scaled
    so that its logits have the sigma (0.9) that ``init``'s 0.02 gives at
    the published ``router_width``."""
    def seeded(model, seed):
        params = model.init(jax.random.key(seed))
        key = jax.random.key(seed + 100)
        for stack in stacks if norms else ():
            layers = params[stack]
            for name in norms:
                key, sub = jax.random.split(key)
                layers[name] = layers[name] + 0.3 * jax.random.normal(
                    sub, layers[name].shape)
            if also is not None:
                key = also(layers, key)
        if router_width is not None:
            params["layers"]["router"] *= (
                router_width / model.cfg.dim) ** 0.5
        return params
    return seeded


def _no_hook(*args, **kw):
    pass


def _dict():
    return dataclasses.field(default_factory=dict)


# The latent and the expert families' engine: ONE a family for the four
# cases below (its programs compile once), so its pool is the smallest a
# case needs: ten blocks, which three requests of 20-odd tokens that
# generate twelve overflow and everything else fits.
ENGINE_KW = dict(max_slots=4, max_seq=64, prefill_buckets=(8, 16, 32),
                 block_size=8)
TEN_BLOCKS = {"num_blocks": 10}


def engine_cases(**other):
    """name -> (prompt lengths, or "shared": two prompts behind one head
    of 16 tokens, a request after the other; tokens out; the stats key
    that must move; engine kwargs; model overrides). ``other`` replaces a
    case."""
    return {
        "bucket_prefill": ((5, 12, 20), 6, "prefills", TEN_BLOCKS, {}),
        "chunked_prefill": ((40, 9), 6, "prefills", TEN_BLOCKS, {}),
        "prefix_prefill": ("shared", 6, "prefix_prefills", TEN_BLOCKS, {}),
        "preemption_by_recompute": ((20, 21, 22), 12, "preemptions",
                                    TEN_BLOCKS, {}),
        **other}


@dataclasses.dataclass(frozen=True, eq=False)
class Family:
    """What a file says of its family. Only ``config`` and ``reference``
    have no default; a group of cases reads the fields under its name."""

    # (dtype=, **overrides) -> the debug configuration
    config: Callable[..., Any]
    # (cfg, params, tokens, **kw) -> the plain reference's logits, op by
    # op; the cases call it as ONE program a variant (``reference``)
    reference: Callable[..., Any]
    # (model, seed) -> params: ``init`` and what the family draws besides
    # (norms, the router's scale); traced, ONE program a model
    seeded: Callable[[Any, Any], Any] = drawn()
    shape: Tuple[int, int] = (2, 24)          # ``seqs``'s, where none is said
    f32_tol: float = 1e-4                     # max |logit difference|
    bf16_rel_rms: float = 0.02                # relative RMS of the logits
    # -- float32_paths: name -> (run(model, params, toks) -> logits, the
    # first position compared), a model a dict of ``overrides``
    paths: Dict[str, Tuple[Callable, int]] = _dict()
    f32_overrides: Tuple[Dict[str, Any], ...] = ({},)
    # -- bf16_forced_routing: (path, seed) cases over name -> run(model,
    # served, toks) -> (logits, experts); ``after_bf16(cfg, model, params,
    # served, toks, experts)`` the family's own assertions
    bf16_paths: Dict[str, Callable] = _dict()
    bf16_cases: Tuple[Tuple[str, int], ...] = ()
    after_bf16: Callable[..., None] = _no_hook
    # -- faulty_block: the names; fault -> (relative RMS, max |difference|)
    # that it has to pass; the path whose honest logits the reference's
    # faults are held against, or ``faulty(fault) -> (got, want)`` where
    # the fault is the system's
    faults: Tuple[str, ...] = ()
    fault_floors: Callable[[str], Tuple[float, float]] = None
    fault_path: str = "prefill_then_paged_decode"
    faulty: Optional[Callable[[str], Tuple[Any, Any]]] = None
    # -- scopes: method -> (scopes its lowered text holds, scopes it must not)
    scopes: Dict[str, Tuple[Tuple[str, ...], Tuple[str, ...]]] = _dict()
    # -- serving_params: the leaves of ``stacks`` that stay float32, a name
    # or a (stack, name); the model's class where the family has one of
    # its own; ``after_serving_params(cfg, model, params, served)``
    stacks: Tuple[str, ...] = ("layers", "leading_layers")
    f32_leaves: frozenset = frozenset()
    model_class: Optional[type] = None
    after_serving_params: Callable[..., None] = _no_hook
    # -- the engine: its sizes; ``engine_cases()``'s cases, of which those
    # of the same kwargs and overrides share ONE engine; a generated token
    # may stand behind the reference's first by less than
    # ``greedy_margin``; ``engine_stats(eng, stats, cfg, model)`` the
    # family's own assertions after a case
    engine_kw: Dict[str, Any] = dataclasses.field(
        default_factory=lambda: ENGINE_KW)
    engine_cases: Dict[str, tuple] = _dict()
    greedy_margin: float = 1e-3
    engine_stats: Callable[..., None] = _no_hook
    # -- a family with a recurrent state (``state_*`` cases): id ->
    # overrides of ``apply``'s patterns; the decode implementations; the tolerances of prefill-then-decode (relative
    # RMS); whether a router's choices are forced on the reference under
    # bf16; ``runs_in_blocks``'s sizes; ``state_stats(eng, stats, impl)``
    # after continuous batching
    patterns: Dict[str, Dict[str, Any]] = _dict()
    state_impls: Tuple[Optional[str], ...] = (None,)
    state_f32_tol: float = 1e-4
    state_bf16_tol: float = 0.04
    state_layers: int = 3              # of the debug model
    routed: bool = False
    runs: Dict[str, Any] = _dict()
    state_stats: Callable[..., None] = _no_hook


# -- the helpers ---------------------------------------------------------------
def vocab(cfg):
    """One past the largest token a prompt holds (a block-diffusion
    model's mask token and what lies behind it are no prompt's)."""
    return getattr(cfg, "mask_token_id", None) or cfg.vocab_size


def seqs(cfg, shape=(2, 24), seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, vocab(cfg), shape), I32)


def tokens_of(cfg, shape, seed=2):
    """The state families' draw (``jax.random``'s, where ``seqs`` is
    numpy's): their tolerances were read on these tokens."""
    return jax.random.randint(jax.random.key(seed), shape, 1, cfg.vocab_size)


def prompt_of(cfg, n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, vocab(cfg), n)]


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def max_abs(got, want):
    return float(jnp.max(jnp.abs(got - want)))


def same_sets(a, b):
    """[..., K] expert ids -> [...] bool: the same experts, any order."""
    return jnp.all(jnp.sort(a, -1) == jnp.sort(b, -1), axis=-1)


@functools.lru_cache(maxsize=None)
def jitted(model, name):
    """``jax.jit`` of a model's method, one a (model, method): a second
    ``jax.jit`` of the same method would compile it again."""
    return jax.jit(getattr(model, name))


def fresh(model):
    """A model of its own for a test that patches what the programs
    trace: the shared model's were traced before the patch."""
    return model_for(model.cfg)


@functools.lru_cache(maxsize=None)
def forced(model, impl):
    """``model`` with its decode attention forced to ``impl`` (None: as
    it is), one a (model, impl)."""
    if impl is None:
        return model
    return model_for(dataclasses.replace(model.cfg, decode_attention=impl))


@functools.lru_cache(maxsize=None)
def _block(family, dtype, overrides):
    cfg = family.config(dtype=dtype, **dict(overrides))
    return cfg, model_for(cfg)


@functools.lru_cache(maxsize=None)
def _params(family, model, seed):
    return jax.jit(functools.partial(family.seeded, model))(seed)


def make(family, dtype=jnp.float32, seed=1, **overrides):
    """(cfg, model, params): ONE (cfg, model) a (family, dtype, overrides)
    and one ``params`` a seed of it, so a model's programs, ``init``
    among them, compile once. Nobody writes into the tree."""
    cfg, model = _block(family, dtype, tuple(sorted(overrides.items())))
    return cfg, model, _params(family, model, seed)


@functools.lru_cache(maxsize=None)
def _reference(family, cfg, flags, traced):
    return jax.jit(lambda params, tokens, arrays: family.reference(
        cfg, params, tokens, **dict(flags), **dict(zip(traced, arrays))))


def reference(family, cfg, params, tokens, **kw):
    """``family.reference`` as ONE program a (cfg, variant) and shape (op
    by op it takes seconds a call at these sizes): a fault's name and the
    like are the variant, arrays (forced experts, forced rows) are
    traced."""
    arrays = {k: v for k, v in kw.items() if hasattr(v, "shape")}
    flags = tuple(sorted((k, v) for k, v in kw.items() if k not in arrays))
    return _reference(family, cfg, flags, tuple(arrays))(
        params, jnp.asarray(tokens), tuple(arrays.values()))


@functools.lru_cache(maxsize=None)
def honest(family, overrides=()):
    """(cfg, model, params, toks, the reference's logits) of the float32
    model at seed 1 on ``seqs(cfg, family.shape)``: what the float32
    paths and the faults are held against, computed once."""
    cfg, model, params = make(family, **dict(overrides))
    toks = seqs(cfg, family.shape)
    return cfg, model, params, toks, reference(family, cfg, params, toks)


@functools.lru_cache(maxsize=None)
def served_logits(family, path):
    """The honest float32 system's logits down ``path``."""
    cfg, model, params, toks, _ = honest(family)
    with jax.default_matmul_precision("highest"):
        return family.paths[path][0](model, params, toks)


def full_forward(model, params, toks):
    return jitted(model, "apply")(params, toks)


def bucket_prefill(model, params, toks):
    B, total = toks.shape
    return jitted(model, "forward_step")(
        params, toks, model.init_kv_cache(B, total), jnp.zeros((B,), I32))[0]


def paged_prefill(model, params, toks, prompt, bs=8, *, width=None,
                  stop_at_lengths=None):
    """``check_logits``'s route as far as the pool: a bucket prefill of
    each row's first ``prompt`` tokens (an int, or a length a row) into a
    slot-major cache ``width`` rows wide (None: the whole sequence's
    blocks), whose rows are then scattered into pool blocks by the POOL'S
    OWN leaf names: ``"k"`` / ``"v"`` hold K/V rows, latent rows or rows
    of words alike; a recurrent model's state rows, which are no pages,
    go over as they are. ``stop_at_lengths`` (recurrent models: the
    prefill is told each row's length, or None) -> (the prefill's logits,
    pool, tables [B, blocks a slot])."""
    B, total = toks.shape
    nb = -(-total // bs)
    width = nb * bs if width is None else width
    lens = np.broadcast_to(np.asarray(prompt), (B,))
    padded = np.zeros((B, width), np.int32)
    for r in range(B):
        padded[r, :lens[r]] = np.asarray(toks)[r, :lens[r]]
    told = () if stop_at_lengths is None else (
        jnp.asarray(lens, I32) if stop_at_lengths else None,)
    pre, cache = jitted(model, "forward_step")(
        params, jnp.asarray(padded), model.init_kv_cache(B, width),
        jnp.zeros((B,), I32), *told)
    state = set(model.state_row_shapes()) if getattr(
        model, "recurrent", False) else set()
    pool = model.init_kv_pool(B * nb + 1, bs, *((B,) if state else ()))
    tables = np.arange(B * nb).reshape(B, nb)
    at = tables[:, :width // bs].reshape(-1)
    pool = {name: cache[name].astype(leaf.dtype) if name in state
            else leaf.at[:, at].set(cache[name].reshape(
                leaf.shape[0], len(at), bs, *leaf.shape[3:]))
            for name, leaf in pool.items()}
    return pre, pool, jnp.asarray(tables, I32)


def prefill_then_paged_decode(model, params, toks, prompt=16, bs=8, *,
                              steps=None, stop_at_lengths=True,
                              handed_on=None, seen=None):
    """``check_logits``'s route: ``paged_prefill``, then paged decode steps
    (a latent model's ABSORBED form; a sliding layer skips the rows behind
    its window; a recurrent model's state rows ride the pool's tree).

    ``steps`` None: every row is prefilled to ``prompt`` -> the logits of
    every position [B, total, V], the prefill's and then a step's each.
    ``steps`` a number (the state families): TWO rows of different lengths
    ``prompt`` in one padded bucket of ``total - steps``, the prefill's
    pages and state placed as the engine places them -> the logits [B,
    steps, V] of the positions behind each row's own prompt.

    ``handed_on(step, pool) -> pool`` stands between the steps (a planted
    fault); ``seen("prefill" | "decode")`` is called after each program
    ran (a test that records what it selected)."""
    B, total = toks.shape
    ragged = steps is not None
    lens = np.broadcast_to(np.asarray(prompt), (B,))
    pre, pool, tables = paged_prefill(
        model, params, toks, prompt, bs,
        width=total - steps if ragged else None,
        stop_at_lengths=stop_at_lengths if ragged else None)
    if seen is not None:
        seen("prefill")
    step = jitted(model, "decode_step_paged")
    rows = np.arange(B)
    out = []
    for i in range(steps if ragged else total - int(lens[0])):
        if handed_on is not None:
            pool = handed_on(i, pool)
        logits, pool = step(params, jnp.asarray(np.asarray(toks)[rows,
                                                                  lens + i]),
                            pool, tables, jnp.asarray(lens + i, I32))
        out.append(logits)
        if seen is not None:
            seen("decode")
    out = jnp.stack(out, 1)
    return out if ragged else jnp.concatenate([pre[:, :lens[0]], out], axis=1)


def paged_decode_with_the_kernel(model, params, toks, **kw):
    """The same route with the decode attention's Mosaic kernel forced
    (interpreted on the CPU)."""
    return prefill_then_paged_decode(forced(model, "pallas"), params, toks,
                                     **kw)


def prefix_prefill(model, params, toks, prefix=8, *, seen=None):
    """The last-token logits of a suffix (chunk) prefill over a cached
    prefix (its rows from a plain prefill: K/V, latent rows, index keys
    with them), padded as the engine pads: 8 rows behind the prefix, the
    suffix in a bucket of 32."""
    B, total = toks.shape
    cache = model.init_kv_cache(B, prefix)
    _, cache = jitted(model, "forward_step")(params, toks[:, :prefix], cache,
                                             jnp.zeros((B,), I32))
    if seen is not None:
        seen("prefix")
    padded = {n: jnp.pad(a, ((0, 0), (0, 0), (0, 8)) + ((0, 0),) * (
        a.ndim - 3)) for n, a in cache.items()}
    suffix = jnp.zeros((B, 32), I32).at[:, :total - prefix].set(
        toks[:, prefix:])
    logits, rows = jitted(model, "prefill_with_prefix")(
        params, suffix, padded["k"], padded["v"], jnp.full((B,), prefix, I32),
        jnp.full((B,), total - prefix, I32))
    assert rows["k"].shape == (cache["k"].shape[0], B, 32) + (
        cache["k"].shape[3:])
    return logits[:, None]                       # position total - 1


def paged_decode_from_empty(model, params, toks, bs=8, *, seen=None):
    """Every position by a counted paged decode step from an empty pool
    (a latent model's ABSORBED attention), with the experts each step
    chose: -> (logits [B, S, V], experts [L_moe, B, S, K])."""
    B, total = toks.shape
    nb = -(-total // bs)
    pool = model.init_kv_pool(B * nb + 1, bs)
    tables = jnp.arange(B * nb, dtype=I32).reshape(B, nb)
    step = jitted(model, "decode_step_paged_counted")
    logits, experts = [], []
    for pos in range(total):
        out, pool, extras = step(params, toks[:, pos], pool, tables,
                                 jnp.full((B,), pos, I32))
        logits.append(out[:, None])
        experts.append(extras["experts"])
        if seen is not None:
            seen("decode")
    return jnp.concatenate(logits, 1), jnp.concatenate(experts, 2)


def bf16_full_forward(model, params, toks):
    logits, extras = jitted(model, "_apply_with_extras")(params, toks)
    return logits, extras["experts"]


def lowered_text(model, method, params):
    """The lowered text, names of scopes and all, of ``forward_step`` or
    ``decode_step_paged`` at two slots."""
    two = jnp.zeros((2,), I32)
    args = {"forward_step": (params, jnp.ones((2, 16), I32),
                             model.init_kv_cache(2, 16), two),
            "decode_step_paged": (params, two, model.init_kv_pool(9, 8),
                                  jnp.zeros((2, 4), I32), two)}[method]
    return jax.jit(getattr(model, method)).lower(*args).as_text(
        debug_info=True)


# -- engines -------------------------------------------------------------------
def engine_of(family, model, params, **kw):
    """A fresh engine of the family's sizes."""
    return ContinuousBatchingEngine(model, params,
                                    **{**family.engine_kw, **kw})


@functools.lru_cache(maxsize=None)
def _shared_engine(family, overrides, kw):
    cfg, model, params = make(family, **dict(overrides))
    return engine_of(family, model, params, **dict(kw))


def shared_engine(family, overrides=None, **kw):
    """ONE engine a (family, model overrides, engine kwargs): its programs
    compile once for every case that uses it. A case reads the counters
    it moves BEFORE and after, so it holds alone and in any order."""
    return _shared_engine(family, tuple(sorted((overrides or {}).items())),
                          tuple(sorted(kw.items())))


def generate(eng, prompts, sampling, one_by_one=False):
    """Float32 compute to the end: all at once, or a request after the
    other (the second finds the first's blocks)."""
    with jax.default_matmul_precision("highest"):
        if one_by_one:
            return [eng.generate([p], sampling)[0] for p in prompts]
        return eng.generate(prompts, sampling)


def alone(family, model, params, prompt, n_out, **kw):
    """A FRESH engine's one request: what the state families' engine
    cases compare with (fresh: no slot, page or index of it has seen
    another request)."""
    return generate(engine_of(family, model, params, **kw), [prompt],
                    SamplingParams(max_tokens=n_out))[0].output


def drive(eng, prompts, outs, each_step=None):
    """Submit every request, then step to the end (later ones are
    admitted while others decode) -> the requests."""
    with jax.default_matmul_precision("highest"):
        reqs = [eng.submit(p, SamplingParams(max_tokens=n))
                for p, n in zip(prompts, outs)]
        while eng.has_work():
            eng.step()
            if each_step is not None:
                each_step(eng)
    return reqs


def reads_first(eng):
    """``eng`` made to read every step before it dispatches the next, as
    an engine with a free slot did before PR 60: the same programs and
    arithmetic with no step ahead, so what a step ahead must not change a
    token of. -> ``eng``."""
    eng._may_run_ahead = lambda active: False
    return eng


def ended(k):
    """A ``drive_arrivals`` due time: once request ``k`` has ended."""
    return lambda reqs: reqs[k].done.is_set()


def drive_arrivals(eng, arrivals):
    """``arrivals`` = [(due, prompt, sampling)]: each is submitted before
    the first ``step()`` at which ``due(requests so far)`` holds (``None``:
    at the start), in order; then step to the end. -> (the requests; how
    many ENDED WITH A STEP AHEAD ON THE DEVICE, each of which has that
    step's row dropped as it is read; how many were ADMITTED under one)."""
    reqs, ended, admitted = [], [0], [0]
    finish, plan = eng._finish, eng._plan_admission

    def counting_finish(slot, reason):
        ended[0] += eng._in_flight is not None
        finish(slot, reason)

    def counting_plan():
        before = eng.stats["admitted"]
        out = plan()
        admitted[0] += (eng.stats["admitted"] - before) * (
            eng._in_flight is not None)
        return out

    eng._finish, eng._plan_admission = counting_finish, counting_plan
    arrivals = list(arrivals)
    with jax.default_matmul_precision("highest"):
        while arrivals or eng.has_work():
            while arrivals and (arrivals[0][0] is None
                                or arrivals[0][0](reqs)):
                _, prompt, sampling = arrivals.pop(0)
                reqs.append(eng.submit(prompt, sampling))
            eng.step()
    return reqs, ended[0], admitted[0]


def moved(eng, before, *keys):
    """What the counters ``keys`` of a shared engine gained since
    ``before`` (a copy of its ``stats``)."""
    return tuple(eng.stats[k] - before[k] for k in keys)


# -- the shared cases --------------------------------------------------------
_CASES = []


def _asked_for(by):
    """Registers a factory ``family -> test function`` with the predicate
    ``by(family)`` that says whether a description asks for its case."""
    def register(factory):
        _CASES.append((by, factory))
        return factory
    return register


def cases_of(family):
    """{name: test function} of every shared case whose fields ``family``
    fills in: ``paths`` the float32 comparison, ``bf16_cases`` the bf16
    one, ``faults`` the refusals, ``scopes``, ``f32_leaves``,
    ``engine_cases`` the greedy tokens; a state family's ``patterns`` the
    model's cases and its ``runs`` the engine's."""
    tests = [factory(family) for by, factory in _CASES if by(family)]
    return {test.__name__: test for test in tests}


# the latent and the expert families
@_asked_for(lambda f: f.paths)
def float32_paths(family):
    cases = [(path, o) for o in family.f32_overrides
             for path in sorted(family.paths)]

    @pytest.mark.parametrize("path,overrides", cases, ids=[
        "-".join([p, *map(str, o.values())]) for p, o in cases])
    def test_float32_compute_matches_the_reference(path, overrides):
        """float32 compute, where nothing swaps: every path's logits to
        ``f32_tol`` of the reference's."""
        cfg, model, params, toks, want = honest(
            family, tuple(sorted(overrides.items())))
        run, first = family.paths[path]
        with jax.default_matmul_precision("highest"):
            got = run(model, params, toks)
        want = want[:, first:]
        assert got.shape == want.shape
        assert max_abs(got, want) < family.f32_tol

    return test_float32_compute_matches_the_reference


@_asked_for(lambda f: f.bf16_cases)
def bf16_forced_routing(family):
    @pytest.mark.parametrize("path,seed", family.bf16_cases)
    def test_bf16_compute_with_the_reference_forced_to_its_routing(path,
                                                                   seed):
        """bf16 compute swaps near-tied experts, and each swap moves that
        token's logits: the reference is FORCED to the system's routing,
        so that what is measured is the arithmetic."""
        cfg, model, params = make(family, jnp.bfloat16, seed)
        toks = seqs(cfg, family.shape, seed=seed)
        served = model.serving_params(params)
        got, experts = family.bf16_paths[path](model, served, toks)
        want = reference(family, cfg, params, toks, forced_experts=experts)
        assert rel_rms(got, want) < family.bf16_rel_rms
        family.after_bf16(cfg, model, params, served, toks, experts)

    return test_bf16_compute_with_the_reference_forced_to_its_routing


@_asked_for(lambda f: f.faults)
def faulty_block(family):
    @pytest.mark.parametrize("fault", family.faults)
    def test_a_faulty_block_is_refused(fault):
        """Each fault, done to the REFERENCE (or planted in the system),
        has to show in the float32 comparison: the system computes the
        published block and not the faulty one."""
        if family.faulty is not None:
            got, want = family.faulty(fault)
        else:
            cfg, _, params, toks, _ = honest(family)
            got = served_logits(family, family.fault_path)
            want = reference(family, cfg, params, toks, fault=fault)
        apart, at_most = family.fault_floors(fault)
        assert rel_rms(got, want) > apart, fault
        assert max_abs(got, want) > at_most, fault

    return test_a_faulty_block_is_refused


@_asked_for(lambda f: f.scopes)
def scopes_in_the_programs(family):
    @pytest.mark.parametrize("method", list(family.scopes))
    def test_scopes_are_in_the_lowered_programs_metadata(method):
        cfg, model, params = make(family)
        text = lowered_text(model, method, params)
        present, absent = family.scopes[method]
        for scope in present:
            assert scope in text, scope
        for scope in absent:
            assert scope not in text, scope

    return test_scopes_are_in_the_lowered_programs_metadata


@_asked_for(lambda f: f.f32_leaves)
def serving_params_leaves(family):
    def test_serving_params_keep_the_float32_leaves():
        cfg, model, params = make(family, jnp.bfloat16)
        if family.model_class is not None:
            assert isinstance(model, family.model_class) and model.recurrent
        served = model.serving_params(params)
        for stack in family.stacks:
            for name, a in served[stack].items():
                f32 = name in family.f32_leaves or (
                    (stack, name) in family.f32_leaves)
                assert a.dtype == (jnp.float32 if f32
                                   else jnp.bfloat16), (stack, name)
        assert served["norm_f"].dtype == jnp.float32
        assert served["embed"].dtype == jnp.bfloat16
        assert cfg.num_params() == sum(a.size
                                       for a in jax.tree.leaves(params))
        again = model.serving_params(served)
        assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                          jax.tree.leaves(served)))
        family.after_serving_params(cfg, model, params, served)

    return test_serving_params_keep_the_float32_leaves


@_asked_for(lambda f: f.engine_cases)
def engine_greedy_tokens(family):
    @pytest.mark.parametrize("case", sorted(family.engine_cases))
    def test_engine_greedy_tokens_are_the_references_argmax(case):
        """Through ``ContinuousBatchingEngine`` in float32 compute: every
        generated token is the reference's first choice given the prompt
        and the tokens before it (teacher forced) unless the reference has
        it within ``greedy_margin`` of its first: a prefix hit reads
        another request's blocks back and gives what the cold path gives,
        a preempted request is recomputed to the same tokens. ONE
        reference forward a case: a row's logits depend on nothing behind
        it, so the sequences go in padded to one length."""
        lens, n_out, counter, kwargs, overrides = family.engine_cases[case]
        cfg, model, params = make(family, **overrides)
        eng = shared_engine(family, overrides, **kwargs)
        if lens == "shared":
            head = prompt_of(cfg, 16, 50)
            prompts = [head + prompt_of(cfg, n, i)
                       for i, n in enumerate((3, 7))]
        else:
            prompts = [prompt_of(cfg, n, i) for i, n in enumerate(lens)]
        before = eng.stats[counter]
        reqs = generate(eng, prompts, SamplingParams(max_tokens=n_out),
                        one_by_one=lens == "shared")
        # at ONE shape, the engine's, so that it compiles once an engine
        padded = np.zeros((eng.max_slots, eng.max_seq), np.int32)
        for i, (p, r) in enumerate(zip(prompts, reqs)):
            assert len(r.output) == n_out
            padded[i, :len(p) + n_out] = p + r.output
        wants = np.asarray(reference(family, cfg, params, padded))
        for prompt, req, want in zip(prompts, reqs, wants):
            want = want[len(prompt) - 1:len(prompt) - 1 + n_out]
            for row, tok in zip(want, req.output):
                assert tok == row.argmax() or (
                    row.max() - row[tok] < family.greedy_margin)
        stats = eng.stats
        assert stats[counter] > before
        family.engine_stats(eng, stats, cfg, model)

    return test_engine_greedy_tokens_are_the_references_argmax


# -- the shared cases: the families with a recurrent state --------------------
LENS, TB, STEPS, BS = (13, 7), 16, 8, 4


def prefill_then_decode(model, params, toks, **kw):
    """``prefill_then_paged_decode`` at the state families' sizes: rows
    of 13 and 7 tokens in a bucket of 16, blocks of 4, 8 steps."""
    return prefill_then_paged_decode(model, params, toks, LENS, BS,
                                     steps=STEPS, **kw)


def wanted(family, cfg, params, toks, **kw):
    want = reference(family, cfg, params, toks, **kw)
    return jnp.stack([want[r, n:n + STEPS] for r, n in enumerate(LENS)])


@functools.lru_cache(maxsize=None)
def honest_state(family):
    """(cfg, model, params, toks, the float32 system's logits down
    ``prefill_then_decode``): what the state families' faults are held
    against."""
    cfg, model, params = make(family)
    toks = tokens_of(cfg, (2, TB + STEPS))
    return cfg, model, params, toks, prefill_then_decode(model, params, toks)


@_asked_for(lambda f: f.patterns)
def state_apply(family):
    @pytest.mark.parametrize("pattern", list(family.patterns))
    def test_apply_is_the_reference(pattern):
        """float32 compute: logits to 1e-4 of the reference's, whose
        recurrence is positional where ``apply``'s is a scan in chunks (21
        positions: a last chunk padded); runs of one layer and of
        several, attention first, last and in the middle."""
        cfg, model, params = make(family, **family.patterns[pattern])
        toks = tokens_of(cfg, (2, 21))
        np.testing.assert_allclose(
            full_forward(model, params, toks),
            reference(family, cfg, params, toks), atol=1e-4, rtol=1e-4)

    return test_apply_is_the_reference


@_asked_for(lambda f: f.patterns)
def state_prefill_then_decode_f32(family):
    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_prefill_then_paged_decode_is_the_reference_float32(impl):
        """float32 compute against the float32 reference, tightly (what
        is left is the order of the sums): the scan stops each row at its
        length, the state rows and pages land where the decode step reads
        them, both implementations of the kernels."""
        cfg, model, params = make(family)
        toks = tokens_of(cfg, (2, TB + STEPS))
        got = prefill_then_decode(forced(model, impl), params, toks)
        assert rel_rms(got, wanted(family, cfg, params, toks)) \
            < family.state_f32_tol

    return test_prefill_then_paged_decode_is_the_reference_float32


@_asked_for(lambda f: f.patterns)
def state_prefill_then_decode_bf16(family):
    def test_prefill_then_paged_decode_bf16_at_a_stated_tolerance():
        """bf16 compute (S float32) against the float32 reference fed the
        same bf16-rounded leaves and, where the family routes, the
        experts the system chose (``apply`` in the system's arithmetic),
        so that what is measured is the arithmetic and not the router's
        near-ties."""
        cfg, model, params = make(family, jnp.bfloat16)
        served = model.serving_params(params)
        toks = tokens_of(cfg, (2, TB + STEPS))
        kw = {}
        if family.routed:
            kw["forced_experts"] = bf16_full_forward(model, served, toks)[1]
        got = prefill_then_decode(model, served, toks)
        assert rel_rms(got, wanted(family, cfg, served, toks, **kw)) \
            < family.state_bf16_tol

    return test_prefill_then_paged_decode_bf16_at_a_stated_tolerance


@_asked_for(lambda f: f.runs)
def state_continuous_batching(family):
    @pytest.mark.parametrize("impl", family.state_impls)
    def test_continuous_batching_over_the_state(impl):
        """Five requests of different lengths through three slots: the
        later ones are admitted while others decode, into slots that
        others have left (whose state rows they must not see); one prompt
        is 2.6 chunks long (chunked prefill, a padded last chunk, the
        state carried from chunk to chunk), one a bucket with padding
        behind it. Streamed greedy tokens equal a fresh engine's, one
        request at a time (``impl``: the kernels, interpreted, and their
        twins)."""
        cfg, model, params = make(family)
        model = forced(model, impl)
        outs = (9, 4, 12, 5, 7)
        prompts = [prompt_of(cfg, n, i)
                   for i, n in enumerate((5, 42, 13, 16, 9))]
        eng = engine_of(family, model, params)
        reqs = drive(eng, prompts, outs)
        for p, n, req in zip(prompts, outs, reqs):
            assert req.output == alone(family, model, params, p, n), len(p)
        stats = eng.stats
        assert stats["state_rows_written"] == 5
        assert stats["state_layers"] == family.state_layers
        # the 42-token prompt: chunks of 16, 16 and 10; two started from a
        # state
        assert stats["state_chunks_carried"] == 2
        assert stats["state_bytes"] == 3 * stats["state_row_bytes"] == sum(
            eng.kv[n].nbytes for n in model.state_row_shapes())
        assert stats["kv_pool_bytes"] == (eng.kv["k"].nbytes
                                          + eng.kv["v"].nbytes)
        assert stats["prefix_hits_refused_recurrent"] == 0
        family.state_stats(eng, stats, impl)

    return test_continuous_batching_over_the_state


@_asked_for(lambda f: f.runs)
def state_preemption(family):
    def test_preemption_by_recompute_rebuilds_the_state():
        """A pool too small for three growing requests: the youngest is
        preempted, its row dropped, and the re-prefill of prompt + output
        rebuilds it: the tokens are an unpreempted run's."""
        cfg, model, params = make(family)
        prompts = [prompt_of(cfg, n, 10 + i)
                   for i, n in enumerate((20, 21, 22))]
        eng = engine_of(family, model, params, num_blocks=10)
        reqs = generate(eng, prompts, SamplingParams(max_tokens=12))
        assert eng.stats["preemptions"] > 0
        for p, req in zip(prompts, reqs):
            assert req.output == alone(family, model, params, p, 12)

    return test_preemption_by_recompute_rebuilds_the_state


@_asked_for(lambda f: f.runs)
def state_prefix_hit_refused(family):
    def test_a_prefix_hit_is_refused_and_counted():
        """Two requests with a shared prefix of two blocks, one after the
        other: the second finds the first's pages in the index and does
        NOT take them (they come without the state at their end); both
        give what they give with an empty cache."""
        cfg, model, params = make(family)
        head = prompt_of(cfg, 16, 50)
        prompts = [head + prompt_of(cfg, n, 60 + i)
                   for i, n in enumerate((3, 7))]
        eng = engine_of(family, model, params)
        reqs = generate(eng, prompts, SamplingParams(max_tokens=6),
                        one_by_one=True)
        for p, req in zip(prompts, reqs):
            assert req.output == alone(family, model, params, p, 6)
        stats = eng.stats
        assert stats["prefix_hits_refused_recurrent"] == 1
        assert stats["prefix_prefills"] == stats["prefix_tokens_reused"] == 0

    return test_a_prefix_hit_is_refused_and_counted


@_asked_for(lambda f: f.runs)
def state_handoff_refused(family):
    def test_the_handoff_is_refused():
        """``prefill_only`` / ``submit_prefilled`` carry K/V rows only."""
        cfg, model, params = make(family)
        eng = engine_of(family, model, params)
        with pytest.raises(NotImplementedError, match="recurrent state"):
            eng.prefill_only([1, 2, 3])
        with pytest.raises(NotImplementedError, match="recurrent state"):
            eng.submit_prefilled([1, 2, 3], {}, None)

    return test_the_handoff_is_refused


def _tables_lie_in_runs(eng):
    """Every live slot's table is made of aligned, contiguous runs, and
    no block is in two slots' (a recurrent model shares none)."""
    run, seen = eng.kv_run, set()
    for slot, alloc in enumerate(eng.allocs):
        if alloc is None:
            continue
        blocks = list(eng._tables[slot, :len(alloc.blocks)])
        assert blocks == alloc.blocks and len(blocks) % run == 0
        for r in range(0, len(blocks), run):
            assert blocks[r] % run == 0
            assert blocks[r:r + run] == list(range(blocks[r],
                                                   blocks[r] + run))
        assert not seen & set(blocks)
        seen |= set(blocks)
    assert len(seen) + eng.pool.num_free == eng.num_blocks // run * run


@_asked_for(lambda f: f.runs)
def state_runs_in_blocks(family):
    def test_blocks_in_runs_decode_what_single_blocks_decode(monkeypatch):
        """The cell's mechanism at debug widths (``family.runs``: the
        requests' ``lens`` prompt and ``outs`` output tokens, ``max_seq``,
        ``num_blocks``, the ``run``): an engine whose kernel (forced,
        interpreted) copies runs of ``run`` blocks, in a pool small enough
        to preempt (a slot that grows into another run finds none, the
        youngest is preempted, refilled later and re-prefilled): every
        table lies in runs at every step, and the greedy tokens equal
        those of an engine whose allocator is told ``run`` 1 (single
        blocks, the parent's layout: ``paged_run_blocks`` patched, a
        test's argument and not a user's) and a fresh engine's, a request
        at a time."""
        lens, outs, max_seq, num_blocks, run = (family.runs[k] for k in (
            "lens", "outs", "max_seq", "num_blocks", "run"))
        cfg, model, params = make(family)
        model = forced(model, "pallas")
        prompts = [prompt_of(cfg, n, 20 + i) for i, n in enumerate(lens)]

        def in_runs(eng):
            _tables_lie_in_runs(eng)
            ahead = eng.stats["kv_blocks_reserved_unfilled"]
            assert ahead <= eng.kv_run * sum(
                a is not None for a in eng.allocs)

        sizes = dict(max_seq=max_seq, num_blocks=num_blocks)
        runs = engine_of(family, model, params, **sizes)
        assert runs.kv_run == runs.pool.run == run
        assert runs.stats["kv_run_blocks"] == run
        assert runs.kv["k"].shape[1] == num_blocks + run   # a scratch RUN
        got = [r.output for r in drive(runs, prompts, outs, in_runs)]
        assert runs.stats["preemptions"] > 0
        assert runs.pool.num_free == num_blocks
        assert runs.stats["kv_blocks_reserved_unfilled"] == 0

        monkeypatch.setattr(type(model), "paged_run_blocks",
                            lambda self, block_size: 1)
        single = engine_of(family, model, params, **sizes)
        assert single.kv_run == single.pool.run == 1
        assert single.stats["kv_run_blocks"] == 1
        assert single.kv["k"].shape[1] == num_blocks + 1
        assert [r.output for r in drive(single, prompts, outs,
                                        in_runs)] == got
        for p, n, out in zip(prompts, outs, got):
            assert out == alone(family, model, params, p, n)

    return test_blocks_in_runs_decode_what_single_blocks_decode


# -- the families that two files serve (``test_x_serving.py`` the model's
# programs, ``test_x_engine.py`` the engine over them): what both need of
# the description; a file adds its own fields with ``dataclasses.replace``
def _deepseek_v32():
    from benchmark.builders import deepseek_v32 as builder
    from benchmark.reference import deepseek_v32 as reference
    from ray_tpu.models import MLAConfig

    def config(dtype, held=(4, 8), **overrides):
        """``held``: experts 4..11 of the router's 16."""
        return MLAConfig.debug_deepseek_v32(
            dtype=dtype, first_expert_held=held[0], experts_held=held[1],
            **overrides)

    def ref_forward(cfg, params, tokens, **kw):
        y = cfg.yarn
        return reference.forward(
            builder.reference_params({"tie_word_embeddings": False}, params),
            tokens, **{**dict(
                qk_nope_head_dim=cfg.qk_nope_head_dim,
                qk_rope_head_dim=cfg.qk_rope_head_dim,
                kv_lora_rank=cfg.kv_lora_rank, rope_theta=cfg.rope_theta,
                yarn=(y.factor, y.original_max_position, y.beta_fast,
                      y.beta_slow),
                mscale_all_dim=cfg.yarn_mscale_all_dim,
                rms_norm_eps=cfg.norm_eps, index_topk=cfg.index_topk,
                top_k=cfg.expert_top_k, n_group=cfg.router_n_group,
                topk_group=cfg.router_topk_group,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob, experts_held=cfg.held),
                **kw})

    # norm scales about 1, the LayerNorm's bias about 0; router logits of
    # sigma 0.9, as the init gives at the published width
    return Family(config=config, reference=ref_forward, shape=(2, 48),
                  seeded=drawn(("kv_norm", "q_norm", "attn_norm", "mlp_norm",
                                "idx_k_norm"), 7168,
                               ("layers", "leading_layers")))


def _sdar():
    from benchmark.reference import sdar as reference
    from ray_tpu.models import MoEConfig

    def ref_forward(cfg, params, tokens, **kw):
        return reference.forward(sdar_ref_params(params), tokens,
                                 cfg.block_length, **sdar_ref_kw(cfg), **kw)

    # seeded norm weights are 1: a fault in a norm would hide behind them
    return Family(config=MoEConfig.debug_sdar, reference=ref_forward,
                  shape=(2, 32), seeded=drawn(
                      ("q_norm", "k_norm", "attn_norm", "mlp_norm"), 2048))


def sdar_ref_params(params):
    return {name: params[name]
            for name in ("embed", "layers", "norm_f", "lm_head")}


def sdar_ref_kw(cfg):
    return dict(rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
                top_k=cfg.expert_top_k, norm_topk_prob=cfg.norm_topk_prob)


STATE_ENGINE = dict(max_slots=3, max_seq=96, prefill_buckets=(8, 16),
                    block_size=8)


def _jamba():
    from benchmark.builders import jamba as builder
    from benchmark.reference import jamba as reference
    from ray_tpu.models import JambaConfig

    def ref_forward(cfg, params, tokens, **kw):
        return reference.forward(
            builder.reference_params({}, params), tokens,
            n_layers=cfg.n_layers, attn_layer_period=cfg.attn_period,
            attn_layer_offset=cfg.attn_offset, num_heads=cfg.n_heads,
            num_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            d_state=cfg.ssm_state, dt_rank=cfg.dt_rank, eps=cfg.norm_eps,
            **kw)

    return Family(config=JambaConfig.debug, reference=ref_forward,
                  stacks=("mamba", "attn"), engine_kw=STATE_ENGINE)


def _nemotron_h():
    from benchmark.builders import nemotron_h as builder
    from benchmark.reference import nemotron_h as reference
    from ray_tpu.models import NemotronHConfig

    def config(dtype, pattern="MEM*EM", **kw):
        """Debug widths: experts 2-5 of 8 held unless said otherwise."""
        kw.setdefault("experts_held", 4)
        kw.setdefault("first_expert_held", 2)
        return NemotronHConfig.debug_hybrid(pattern, dtype=dtype, **kw)

    def ref_forward(cfg, params, tokens, **kw):
        return reference.forward(
            builder.reference_params({}, params), tokens,
            pattern=cfg.pattern, mamba_heads=cfg.mamba_heads,
            mamba_head_dim=cfg.mamba_head_dim, n_groups=cfg.ssm_groups,
            ssm_state=cfg.ssm_state, num_heads=cfg.n_heads,
            num_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            top_k=cfg.expert_top_k,
            routed_scaling_factor=cfg.routed_scaling_factor,
            norm_topk_prob=cfg.norm_topk_prob, eps=cfg.norm_eps,
            experts_held=cfg.held, **kw)

    return Family(config=config, reference=ref_forward, routed=True,
                  stacks=("mamba", "attn", "moe"), engine_kw=STATE_ENGINE)


def _lfm2():
    from benchmark.builders import lfm2 as builder
    from benchmark.reference import lfm2 as reference
    from ray_tpu.models import Lfm2Config

    def config(dtype, pattern="ccacca", **kw):
        return Lfm2Config.debug(pattern, dtype=dtype, **kw)

    def ref_forward(cfg, params, tokens, **kw):
        return reference.forward(
            builder.reference_params({}, params), tokens,
            layer_types=cfg.mixer_types,
            num_dense_layers=cfg.num_dense_layers, num_heads=cfg.n_heads,
            num_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            top_k=cfg.expert_top_k, norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
            rope_theta=cfg.rope_theta, eps=cfg.norm_eps, **kw)

    return Family(config=config, reference=ref_forward, routed=True,
                  stacks=("conv_dense", "conv_moe", "attn_moe"),
                  state_layers=4, engine_kw=STATE_ENGINE)


DEEPSEEK_V32, SDAR, JAMBA, NEMOTRON_H, LFM2 = (
    _deepseek_v32(), _sdar(), _jamba(), _nemotron_h(), _lfm2())
