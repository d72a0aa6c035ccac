"""The check refuses what it must: the fp8 control, and the faults the
served path can have, planted underneath a rehearsed run."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_rehearsal import CELLS, rehearse

ROOT = Path(__file__).resolve().parents[2]


def rehearsal_limit(cell):
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    w = next(w for w in bm["workloads"] if w["name"] == cell)
    c = next(c for c in bm["configs"] if c["name"] == w["config"])
    return json.loads((ROOT / c["file"]).read_text())["rehearsal"]["max_logit_gap"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_where_the_program_passes(cell, capsys):
    """The fp8 control, put in the program's place, makes the run not
    correct, on the sample where the program's own tokens pass."""
    line = rehearse(cell, 23, control=True)
    limit = rehearsal_limit(cell)
    assert not line["correct"]
    assert list(line["check"]) == ["control_max_logit_gap"]
    assert line["check"]["control_max_logit_gap"]["value"] > limit
    check = [json.loads(s) for s in capsys.readouterr().out.splitlines()
             if s.startswith('{"line": "check"')][-1]
    assert check["max_logit_gap"] <= limit


def altered_token(eng, sched):
    """A token altered where it is produced: every 5th decode step hands
    back each slot's token plus one."""
    fn, n = eng.decode_slots, [0]

    def step(cache, tokens, active, corrupt=None):
        nxt, ok, cache = fn(cache, tokens, active, corrupt)
        n[0] += 1
        if n[0] % 5 == 0:
            nxt = (nxt + 1) % eng.cfg.vocab_size
        return nxt, ok, cache
    eng.decode_slots = step


def state_unchanged(eng, sched):
    """A decode step that returns the page pool it was given: the new
    token's K/V is never kept."""
    fn = eng.decode_slots

    def step(cache, tokens, active, corrupt=None):
        nxt, ok, _ = fn(jax.tree.map(jnp.copy, cache), tokens, active, corrupt)
        return nxt, ok, cache
    eng.decode_slots = step


def half_batch(eng, sched):
    """Half of the batch left out: every other slot is masked off the step,
    and what the host reads back for it is the row it sent in."""
    fn = eng.decode_slots

    def step(cache, tokens, active, corrupt=None):
        act = np.asarray(active, bool).copy()
        act[1::2] = False
        nxt, ok, cache = fn(cache, tokens, act, corrupt)
        left_out = np.asarray(active, bool) & ~act
        return jnp.where(left_out, jnp.asarray(tokens), nxt), ok, cache
    eng.decode_slots = step


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [altered_token, state_unchanged, half_batch])
def test_fault_is_refused(cell, fault):
    line = rehearse(cell, 31, patch=fault)
    assert not line["correct"], line["check"]
