"""A whole serving run on the CPU with the timed path broken underneath:
``correct`` must come out false for each fault a serving cell can have,
and true for the sound engine."""
import jax
import jax.numpy as jnp
import pytest

CELLS = ["serve.mistral-nemo-12b.decode-heavy"]


def stale_state(engine):
    """The decode step returns its cache unchanged: no key or value of the
    new token is written, and positions do not advance."""
    step = engine._decode

    def broken(p, c, t):
        keep = jax.tree_util.tree_map(jnp.copy, c)
        _, logits = step(p, c, t)
        return keep, logits

    engine._decode = broken


def altered_token(engine):
    """Every fourth decode step emits, in every row, the token after the
    one the model ranks first."""
    step = engine._decode
    calls = [0]

    def broken(p, c, t):
        c, logits = step(p, c, t)
        calls[0] += 1
        if calls[0] % 4 == 0:
            logits = jnp.roll(logits, 1, axis=-1)
        return c, logits

    engine._decode = broken


@pytest.mark.parametrize("cell", CELLS)
def test_sound_engine_is_correct(tiny_run, cell):
    res = tiny_run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", [stale_state, altered_token])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_engine_is_not_correct(tiny_run, cell, fault):
    res = tiny_run(cell, breaker=fault)
    assert not res["correct"], res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_traced_run_reports_what_it_can_read(tiny_run):
    """With ``--trace 1`` the metrics are the cell's per-layer ones; on
    the CPU the trace holds no device plane, so every device-trace metric
    is left out rather than reported as 0."""
    res = tiny_run(CELLS[0], trace=1)
    assert res["correct"]
    got = res["metrics"]
    assert set(got) == {"serve_decode.mfu"}
    assert 0 < got["serve_decode.mfu"]["value"] <= 100
    assert "decode_tok_s" not in got
