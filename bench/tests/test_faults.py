"""The comparison catches a timed path broken underneath: each fault below
is planted in the program for one CPU run of the harness, and ``correct``
has to come out false. (A fault in an exchange between chips has no place
here: both cells run on one chip.)"""

import pytest

from helpers import last_line, load_harness, make_tree


def _unchanged_step(monkeypatch):
    """The watershed task hands its input state on unchanged."""
    from repro.app import pipeline

    monkeypatch.setattr(pipeline, "_t_watershed", lambda state, minSPL, WConn: dict(state))


def _half_the_batch(monkeypatch):
    """Half of each bucket's runs get the first run's output, as if the
    reuse engine had executed only the other half."""
    from repro.engine import streaming

    orig = streaming.execute_bucket

    def broken(bucket, state, cache=None, *, scope=None):
        results, executed, hits = orig(bucket, state, cache, scope=scope)
        rids = sorted(results)
        for rid in rids[1::2]:
            results[rid] = results[rids[0]]
        return results, executed, hits

    monkeypatch.setattr(streaming, "execute_bucket", broken)


def _altered_answer(monkeypatch):
    """The final task's mask comes out one pixel off to the right, as from
    an off-by-one in a kernel."""
    import jax.numpy as jnp

    from repro.app import pipeline

    orig = pipeline._t_area_final

    def broken(state, minSS, maxSS):
        mask = orig(state, minSS, maxSS)["mask"]
        return {"mask": jnp.roll(mask, 1, axis=1)}

    monkeypatch.setattr(pipeline, "_t_area_final", broken)


def _altered_dice(monkeypatch):
    """The comparison stage computes Jaccard where it should compute Dice."""
    import repro.core

    monkeypatch.setattr(repro.core, "dice", repro.core.jaccard)


FAULTS = [_unchanged_step, _half_the_batch, _altered_answer, _altered_dice]


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """The cells at 192-pixel tiles, with the committed traffic and limits."""
    return make_tree(tmp_path_factory.mktemp("faults"), size=192)


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("cell", ["moat800-t1-4k.hybrid", "vbd7990-t1-4k.hybrid"])
def test_fault_is_not_correct(tree, monkeypatch, capsys, fault, cell):
    h = load_harness(tree, monkeypatch)
    fault(monkeypatch)
    assert h.main(["--workload", cell, "--seed", "11", "--seconds", "1", "--trace", "0"]) == 0
    line = last_line(capsys.readouterr()[0])
    assert line["correct"] is False, line["compared"]


@pytest.mark.parametrize("cell", ["moat800-t1-4k.hybrid", "vbd7990-t1-4k.hybrid"])
def test_sound_run_is_correct(tree, monkeypatch, capsys, cell):
    h = load_harness(tree, monkeypatch)
    assert h.main(["--workload", cell, "--seed", "11", "--seconds", "1", "--trace", "0"]) == 0
    assert last_line(capsys.readouterr()[0])["correct"] is True
