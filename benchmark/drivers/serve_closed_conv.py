"""``serve_closed_state`` for a model whose recurrent state is a SHORT
CONVOLUTION's rows and whose FFNs ROUTE (``ray_tpu/models/lfm2.py``: the
cache tree holds ``"conv"`` and no ``"ssm"``; eight of ten layers choose 4
of 64 experts by a biased sigmoid score): the same admission, window,
close, stall watch and record, by ``serve_closed_state``'s own
``closed_loop``; before the window a check of its own.

WHY ITS OWN. ``serve_closed_state.check_logits_state`` reads the first
layer's rows as ``cache["ssm"][0, r]`` through ``model.state_heads``
(Mamba's names) and raises on this model's tree. And its ONE number for
the logits, against a reference that routes by itself, reads 0.13-0.19
here whatever the arithmetic does: under bf16 compute the 4th and 5th of
64 scores swap where nearly tied, a swapped expert is a quarter of a
layer's FFN, and one swap in a layer sends every layer behind it another
way. A limit over that floor passes a program that drops the head norms or
the selection bias (PR 61's first review). ``check_logits_state`` below
therefore holds the two things APART:

- THE ARITHMETIC, with the routing taken out: the reference is FORCED to
  the experts the system chose at every position of both sequences (the
  prompt's, by the model's ``forward_step_counted``, the program
  ``_prefill_impl`` runs with its counters handed back; the decode
  steps', by ``decode_step_paged_counted``, the program the engine's step
  runs) and weighs them by ITS OWN scores of them. ``logits_rel_rms`` is
  the relative RMS of the paged bf16 logits against that: bf16's own
  rounding through ten layers, and everything that is not the choice of
  experts (the filter and its state, the gates, the head norms, the
  scores' renormalisation, the pages, the weights' precision).
- THE CHOICE: ``routing_agreement``, the share of (expert layer, true
  position) at which the reference's OWN top-k of ``score + bias``, from
  the hidden state it had there, is the system's SET. Near-ties fall
  either way (honest: about nine in ten agree); a router of another form,
  or one without its bias, chooses otherwise at a large share of them.
- The first conv layer's STATE rows ``[K-1, D]`` after
  ``state.decode_steps`` steps against the builder's
  ``reference_first_state`` (the reference's own ``g`` over the tokens the
  row has consumed), a row of the state as one "head" of ``worst_head``.
  No router stands before it and nothing accumulates in it.

The reference routing by itself is read too (``logits_rel_rms_own_routing``,
``routing_agreement_own_routing``) and limits nothing: it is the routing
floor at the cell's size, in every run's record.

The sequences, the bucket of two unequal lengths, the placement through
the engine's own ``_prefill_impl`` / ``_insert_impl`` /
``_write_state_impl``, the decode batch of ``DECODE_ROWS`` and the
comparison of the logits are ``serve_closed_state``'s; the tables lie in
the runs the engine's kernel copies (``engine.kv_run``).

``run`` swaps the check into ``serve_closed_state`` for the call (that
module's ``deploy_and_check`` looks it up when it runs) and back: one
process runs one cell. It keeps ``moe_expert_load`` in the record (64
experts x 8 layers a copy: a seventh of ``olmoe``'s), so
``moe.expert_load_max_over_mean.decode`` reads here. A later ``benchmark``
PR should let ``serve_closed_state`` take its check from the builder; THIS
FILE'S ``run`` THEN GOES (PERF.md, Open questions).

Traffic ``kind``: ``"serve_closed_conv"``; parameters:
``serve_closed_state``'s, and ``correctness.routing.min_agreement``.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.drivers import serve_closed_state as base
from benchmark.drivers.serve_closed_state import (DECODE_ROWS, SHORTER_BY,
                                                  worst_head)


def same_sets(a, b):
    """a, b [..., K] expert ids -> [...] bool: the same SET chosen."""
    return np.all(np.sort(a, -1) == np.sort(b, -1), axis=-1)


def check_logits_state(server, reference_forward, reference_first_state, *,
                       seed: int, prompt_len: int, decode_steps: int,
                       tol_rel_rms: float, state_steps: int,
                       tol_state: float, min_routing_agreement: float
                       ) -> dict:
    """Two seeded sequences of ``prompt_len`` and ``prompt_len -
    SHORTER_BY`` prompt tokens (module docstring): bucket prefill with the
    rows' true lengths, the engine's own placement of pages and state,
    ``state_steps`` (>= ``decode_steps``) decode steps over the whole
    cache tree fed each sequence's own next tokens; the logits of the
    first ``decode_steps`` of those positions against the reference
    forced to the system's experts, the experts against the reference's
    own, the first layer's state rows after the last step against
    ``reference_first_state``."""
    import jax
    import jax.numpy as jnp

    engine = server.engine
    model, params, bs = server.model, engine.params, engine.block_size
    kv_run = engine.kv_run
    lens = np.asarray([prompt_len, prompt_len - SHORTER_BY], np.int32)
    total, steps = prompt_len + decode_steps, state_steps
    rng = np.random.default_rng([seed % (2**63), 777])
    # two draws, so that the logits' tokens do not depend on how many
    # steps the state's comparison runs on behind them
    seqs = np.concatenate(
        [rng.integers(1, model.cfg.vocab_size, (2, n)).astype(np.int32)
         for n in (total, steps - decode_steps)], axis=1)
    # the prefill's bucket holds the logits' positions; the tables reach
    # as far as the state's steps go, in whole runs
    nb_prefill = -(-total // bs)
    nb_slot = -(-(prompt_len + steps) // (bs * kv_run)) * kv_run
    n_blocks = 2 * nb_slot
    padded = np.zeros((2, nb_prefill * bs), np.int32)
    for r in range(2):
        padded[r, :lens[r]] = seqs[r, :lens[r]]
    own = np.arange(n_blocks).reshape(2, nb_slot)

    @jax.jit
    def prefill_and_place(params, tokens, lengths):
        _, small = engine._prefill_impl(params, tokens, lengths)
        # what each prompt position's routers chose: the program
        # ``_prefill_impl`` runs, with its counters handed back
        chosen = model.forward_step_counted(
            params, tokens, model.init_kv_cache(*tokens.shape),
            jnp.zeros((2,), jnp.int32),
            *((lengths,) if engine.recurrent else ()))[2]["experts"]
        # (the scratch the idle slots' tables point at is a run too)
        cache = model.init_kv_pool(n_blocks + kv_run, bs, DECODE_ROWS)
        cache = engine._insert_impl(
            cache, small, jnp.asarray(own[:, :nb_prefill].reshape(-1)))
        return engine._write_state_impl(cache, small, jnp.arange(2)), chosen

    # (the cache donated, as the engine's step donates it)
    decode = jax.jit(functools.partial(model.decode_step_paged_counted,
                                       run=kv_run), donate_argnums=2)
    cache, chosen = prefill_and_place(params, jnp.asarray(padded),
                                      jnp.asarray(lens))
    # rows 2.. are IDLE slots, as an engine's are: their tables point at
    # the scratch run, their state rows hold zeros
    tables = np.full((DECODE_ROWS, nb_slot), n_blocks, np.int32)
    tables[:2] = own
    tables = jnp.asarray(tables)
    rows = np.arange(2)
    got, stepped = [], []
    for i in range(steps):
        # fresh arrays a step: a dispatched step may still read its inputs
        tokens, offsets = (np.zeros(DECODE_ROWS, np.int32) for _ in range(2))
        tokens[:2], offsets[:2] = seqs[rows, lens + i], lens + i
        logits, cache, extras = decode(params, jnp.asarray(tokens), cache,
                                       tables, jnp.asarray(offsets))
        if i < decode_steps:
            got.append(logits[:2])
            stepped.append(extras["experts"][:, :2, 0])     # [Le, 2, K]
    got = jnp.stack(got, axis=1).astype(jnp.float32)   # [2, decode_steps, V]
    at = lens[:, None] + np.arange(decode_steps)[None, :]
    # the system's experts at every position, [Le, 2, total, K]: the
    # prompt's, and behind each row's own length the decode steps'
    forced = np.array(jax.device_get(chosen)[:, :, :total])
    forced[:, rows[:, None], at] = np.stack(
        jax.device_get(stepped), axis=2)
    true = np.arange(total)[None, :] < (lens + decode_steps)[:, None]

    @jax.jit
    def reference(params, tokens, forced_experts=None):
        rows_of = reference_forward(params, tokens,
                                    forced_experts=forced_experts)
        return rows_of[rows[:, None], at], rows_of.experts

    def against(want):
        diff = got - want
        return (float(jnp.sqrt(jnp.mean(diff ** 2) / jnp.mean(want ** 2))),
                float(jnp.max(jnp.abs(diff))),
                float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(want, -1))))

    def agreement(chose):
        return float(np.mean(same_sets(jax.device_get(chose), forced)[
            :, true]))

    tokens = jnp.asarray(seqs[:, :total])
    want, chose = reference(params, tokens, jnp.asarray(forced))
    rel_rms, max_abs, argmax_same = against(want)
    agree = agreement(chose)
    want, chose = reference(params, tokens)
    own_rel_rms, _, own_argmax_same = against(want)
    own_agree = agreement(chose)
    finite = bool(jnp.all(jnp.isfinite(got)))

    # the first conv layer's rows after ``steps`` steps: each row has
    # consumed its prompt and ``steps`` tokens more
    first_state = jax.jit(reference_first_state)
    worst = []
    for r in range(2):
        want_rows = jax.device_get(first_state(
            params, jnp.asarray(seqs[r:r + 1, :lens[r] + steps])))
        # [K-1, 1, D]: a row of the state a "head"
        worst.append(worst_head(
            np.asarray(cache["conv"][0, r].astype(jnp.float32))[:, None],
            np.asarray(want_rows)[:, None]))
    # (numpy's max: a NaN in either row is the reading, and fails)
    worst = float(np.max(worst))
    return {"ok": (finite and rel_rms <= tol_rel_rms
                   and agree >= min_routing_agreement and worst <= tol_state),
            "logits_rel_rms": rel_rms, "logits_max_abs_diff": max_abs,
            "argmax_agreement": argmax_same, "tolerance_rel_rms": tol_rel_rms,
            "positions": 2 * decode_steps,
            "routing_agreement": agree,
            "min_routing_agreement": min_routing_agreement,
            "routing_choices": int(true.sum()) * forced.shape[0],
            "logits_rel_rms_own_routing": own_rel_rms,
            "argmax_agreement_own_routing": own_argmax_same,
            "routing_agreement_own_routing": own_agree,
            "state_conv_worst_row_rel_rms": worst,
            "state_steps": steps, "tolerance_state_rel_rms": tol_state}


def run(run) -> dict:
    """``serve_closed_state.closed_loop`` with the check above; the record
    whole (module docstring)."""
    kept = base.check_logits_state
    base.check_logits_state = functools.partial(
        check_logits_state, min_routing_agreement=float(
            run.traffic["correctness"]["routing"]["min_agreement"]))
    try:
        return base.closed_loop(run)
    finally:
        base.check_logits_state = kept
