"""The plain reference: its hashing agrees with the program's, and the MAT
check's allowance for a division one float32 step off."""

import numpy as np

from bench import reference
from bench.suffix import mat


def test_keys_slots_shards_match_the_program():
    from repro.core import stageir
    from repro.flowstate.registers import hash_slot_np
    from repro.serve.sharded import shard_of_key

    rng = np.random.default_rng(3)
    rows = np.zeros((4096, 4), np.float32)
    rows[:, 0] = rng.integers(0, 1 << 24, 4096)
    keys = reference.flow_keys(rows)
    fk = stageir.FlowKey(key_cols=(0,), n_slots=1 << 20)
    assert np.array_equal(keys, fk.apply_keys_np(rows))
    assert np.array_equal(reference.slot_of(keys, 1 << 20),
                          hash_slot_np(keys, 1 << 20))
    assert np.array_equal(reference.shard_of(keys, 4), shard_of_key(keys, 4))


def _params():
    p = {"edges": np.tile(np.float32([0.25, 0.5, 0.75]), (2, 1)),
         "tables": np.zeros((2, 4, 2), np.float32),
         "label_map": np.asarray([0, 1], np.int64)}
    # feature 1's bucket decides: buckets 0-1 score id 0, 2-3 id 1
    p["tables"][1, :2, 0] = 1.0
    p["tables"][1, 2:, 1] = 1.0
    return p


def test_mat_possible_one_step_from_an_edge():
    p = _params()
    z = np.zeros((3, 2), np.float32)
    z[0, 1] = 0.5                                # on the edge: bucket 1
    z[1, 1] = np.nextafter(np.float32(0.5), np.float32(1))  # just above
    z[2, 1] = 0.6                                # far from any edge
    lo = np.nextafter(z, np.float32(-np.inf))
    hi = np.nextafter(z, np.float32(np.inf))
    lo[:, 0], hi[:, 0] = z[:, 0], z[:, 0]
    s = mat.scores(z, p)
    assert list(mat.verdicts(s, p)) == [0, 1, 1]
    ok = mat.possible(z, lo, hi, s, p, {})
    assert ok.tolist() == [[True, True], [True, True], [False, True]]


def test_mitigation_hits_range_for_an_either_way_drop():
    spec = {"threshold": 2, "mode": "drop", "attack_class": 1}
    keys = np.asarray([7, 7, 7, 7])
    groups = np.zeros(4, np.int64)
    verdicts = np.asarray([1, 1, 0, 1])
    either = np.asarray([False, False, True, False])
    out, tk, tr, _, hi = reference.replay_mitigation(
        keys, groups, verdicts, spec, np.float32, either)
    # marked after two hits: the last two packets are dropped
    assert out.tolist() == [1, 1, reference.MITIGATED, reference.MITIGATED]
    assert tk.tolist() == [7]
    # the third packet may or may not have counted: hits in [3, 4]
    assert tr[0].tolist() == [3.0, 2.0] and hi.tolist() == [4.0]
