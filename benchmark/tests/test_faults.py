"""The check catches the timed path broken underneath: each fault a cell
can have is planted in the program, the rest of a run goes as usual (past
the harness's look for a chip), and ``correct`` comes out false."""

import jax
import jax.numpy as jnp
import pytest

from benchmark.tests.test_rehearsal import rehearse


@pytest.fixture(autouse=True)
def _fresh_programs():
    """A planted fault must be traced into the tick, not served from the
    in-memory jit cache of an earlier test (and must not leak out)."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _storm_leg(monkeypatch, wrap):
    from fluidframework_tpu.server import storm as storm_mod

    orig = storm_mod._map_leg
    monkeypatch.setattr(storm_mod, "_map_leg",
                        lambda state, words, lo, hi, seq0: wrap(
                            orig, state, words, lo, hi, seq0))


def _state_unchanged(orig, state, words, lo, hi, seq0):
    return state


def _half_left_out(orig, state, words, lo, hi, seq0):
    return orig(state, words, lo, lo + (hi - lo) // 2, seq0)


def _answer_altered(orig, state, words, lo, hi, seq0):
    out = orig(state, words, lo, hi, seq0)
    return out._replace(value=out.value ^ 1)


def _one_chip_left_out(orig, state, words, lo, hi, seq0):
    """The last quarter of the docs (the last chip's shard) never gets
    its fold back."""
    out = orig(state, words, lo, hi, seq0)
    rows = jnp.arange(out.value.shape[0]) >= 3 * out.value.shape[0] // 4
    pick = lambda new, old: jnp.where(
        rows.reshape((-1,) + (1,) * (new.ndim - 1)), old, new)
    return jax.tree.map(pick, out, state)


STORM_FAULTS = [_state_unchanged, _half_left_out, _answer_altered]


@pytest.mark.parametrize("fault", STORM_FAULTS, ids=lambda f: f.__name__)
def test_storm_fault_is_caught(monkeypatch, fault):
    _storm_leg(monkeypatch, fault)
    result, checks = rehearse("map_storm_10k.flood")
    assert not result["correct"], checks


def test_storm_wal_losing_words_is_caught(monkeypatch):
    from fluidframework_tpu.server import durable_store

    orig = durable_store.GroupCommitLog.append

    def append(self, data, on_durable=None):
        if isinstance(data, list) and len(data) > 1:
            data = data[:1]  # the tick's words never reach the log
        return orig(self, data, on_durable)

    monkeypatch.setattr(durable_store.GroupCommitLog, "append", append)
    result, checks = rehearse("map_storm_10k.flood")
    assert not result["correct"], checks


@pytest.mark.parametrize("fault", [_state_unchanged, _answer_altered,
                                   _one_chip_left_out],
                         ids=lambda f: f.__name__)
def test_sharded_fault_is_caught(monkeypatch, fault):
    _storm_leg(monkeypatch, fault)
    result, checks = rehearse("map_sharded_4chip.flood")
    assert not result["correct"], checks

