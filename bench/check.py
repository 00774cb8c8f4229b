"""The comparison that decides ``correct``.

What the timed path produced is set beside the plain reference
(``bench/reference.py``), computed after the window on the same tiles:

- ``mask_diff_share``: over each tile's default-parameter mask and a sample
  of the last group's final masks drawn from the seed (:func:`sample_ids`), the largest share of
  pixels in which the program's mask and the reference's differ. It covers
  the reuse engine (a merged or cached prefix handed to the wrong run shows
  as a wrong mask) and the operators as they ran on the chip.
- ``dice_gap``: over the same sample, the largest gap between the Dice that
  the window's comparison stage computed and the reference's Dice of its
  own masks.

Each number has its limit in the configuration's ``limits``; PERF.md gives
the readings each limit was set from.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

import reference

NUMBERS = ("mask_diff_share", "dice_gap")


def sample_ids(seed: int, group: int, n_tiles: int, n_runs: int, per_tile: int) -> List[Tuple[int, int]]:
    """The evaluations compared, as ``(tile, run)`` pairs: on every tile, one
    run drawn from the seed out of each of ``per_tile`` contiguous strata of
    the group's runs, so that each half of a group is checked on each tile."""
    out = []
    for i in range(n_tiles):
        rng = np.random.default_rng([seed % 2**63, group, i])
        for part in np.array_split(np.arange(n_runs), per_tile):
            out.append((i, int(rng.choice(part))))
    return out


def mismatch_share(a, b) -> float:
    a, b = np.asarray(a), np.asarray(b)
    return float(np.count_nonzero(a != b)) / a.size


def reference_answers(
    tiles: Sequence[np.ndarray],
    default: Dict[str, Any],
    sample: Sequence[Tuple[int, Dict[str, Any]]],
    dtype,
) -> Tuple[List[Any], List[Tuple[Any, float]]]:
    """The reference's default mask of every tile, and for each sampled
    ``(tile index, params)`` its mask and its Dice against that default."""
    import jax

    defaults = []
    for tile in tiles:
        raw = jax.device_put(tile)
        defaults.append(np.asarray(reference.segment(raw, default, dtype)))
        del raw
    answers = []
    for i, params in sample:
        raw = jax.device_put(tiles[i])
        mask = reference.segment(raw, params, dtype)
        d = float(reference.dice(mask, defaults[i]))
        answers.append((np.asarray(mask), d))
        del raw, mask
    return defaults, answers


def compare(
    prog_defaults: Sequence[Any],
    prog_sample: Sequence[Tuple[Any, float]],
    ref_defaults: Sequence[Any],
    ref_sample: Sequence[Tuple[Any, float]],
) -> Dict[str, float]:
    """The numbers compared: ``(mask, dice)`` pairs in the same order."""
    diffs = [mismatch_share(p, r) for p, r in zip(prog_defaults, ref_defaults)]
    diffs += [mismatch_share(p, r) for (p, _), (r, _) in zip(prog_sample, ref_sample)]
    gaps = [abs(pd - rd) for (_, pd), (_, rd) in zip(prog_sample, ref_sample)]
    return {"mask_diff_share": max(diffs), "dice_gap": max(gaps, default=0.0)}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``correct`` and each number beside its limit."""
    shown = {k: {"value": numbers[k], "limit": limits[k]} for k in NUMBERS}
    return all(numbers[k] <= limits[k] for k in NUMBERS), shown
