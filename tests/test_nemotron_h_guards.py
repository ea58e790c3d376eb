"""Beside ``tests/test_nemotron_h_engine.py``, in a file of its own so
that the suite's workers share the load: the timed-path tool of the
state-space cell at debug widths, honest and with its planted fault."""

import json

import pytest


@pytest.mark.parametrize("fault", [False, True])
def test_the_timed_path_check_holds_layer_zero_state_and_sees_a_fault(
        fault, capsys):
    """``tools/ssm_timed_path_check.py`` at debug widths: an engine's own
    chunked prefills (the state carried from chunk to chunk, activation
    writing the slot's row) and decode steps leave layer 0's state rows
    (``S`` and the convolution's window) where the reference's float32
    recurrence, a position at a time, puts them; a prefill that hands on
    a zero ``S`` moves them far off."""
    from tools import ssm_timed_path_check
    assert ssm_timed_path_check.main(
        ["--tiny-cpu"] + ["--fault"] * fault) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["fault"] is fault and out["decode_steps"] >= 5
    assert out["state_chunks_carried"] == out["state_rows_written"] == 4
    assert (out["worst"] > 0.2) if fault else (out["worst"] < 1e-4)
