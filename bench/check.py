"""How ``correct`` is decided: the served output against the reference.

Once the window has closed, a sample of table rows is drawn from the seed:
``sample_slots`` rows at random and the ``busiest_slots`` rows that took
the most packets (the deepest same-slot chains).  Every packet the engine
served into those rows, warm-up, window and tail alike, goes through the
plain reference (``bench/reference.py`` and the suffix kind's reference)
in arrival order, from an empty table.  The numbers compared:

* ``table_words_off``: words of the sampled rows' final keys and
  registers (and action-table keys and ``[hits, since]``) that differ
  from the reference's.  Registers take only float32 additions and
  power-of-two scalings, exact on the chip, so the limit is 0.
* ``verdict_gap`` (MLP suffix): the widest gap by which the served
  class's reference logit lies below the reference's best, over the
  sampled packets; the served class must be one the model has.
* ``verdict_mismatch`` (MAT suffix): sampled packets whose served verdict
  is not one the reference reaches (``MITIGATED`` included) when each
  divided readout input is exact or one float32 step off: the chip's
  float32 division is exact to one step, not correctly rounded.
* ``unanswered``: packets handed to the engine that got no verdict.
* ``non_fused_batches``: batches not served by the one fused launch
  (``pallas-fused-flow``).
* ``window_compiles``: programs lowered inside the window (each a compile
  or a cache load); set-up warms every shape, so the limit is 0.

``control=True`` puts the reference, computed in the next precision down
(bfloat16 registers, three-pass matmuls), in the program's place.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import reference

FUSED = "pallas-fused-flow"


@dataclasses.dataclass
class Sample:
    index: np.ndarray          # served positions of the sampled packets
    keys: np.ndarray           # their flow keys
    groups: np.ndarray         # their table rows (shard * S + slot)
    rows: np.ndarray           # their packet rows
    n_groups: int
    deepest: int               # most packets in one sampled row


def draw_sample(lap, n_served: int, config: dict, n_shards: int,
                seed: int) -> Sample:
    S = int(config["n_slots"])
    ids = lap.flow_ids(0, n_served).astype(np.float32)
    keys = reference.flow_keys(ids[:, None])
    groups = reference.group_of(keys, S, n_shards)
    n_rows = S * n_shards
    ck = config["check"]
    rng = np.random.default_rng([int(seed), 2])
    chosen = rng.choice(n_rows, size=min(int(ck["sample_slots"]), n_rows),
                        replace=False)
    counts = np.bincount(groups, minlength=n_rows)
    busiest = np.argsort(-counts, kind="stable")[:int(ck["busiest_slots"])]
    chosen = np.union1d(chosen, busiest)
    index = np.flatnonzero(np.isin(groups, chosen))
    rows = lap.take_rows(index)
    return Sample(index, keys[index], groups[index], rows, len(chosen),
                  int(counts[chosen].max(initial=0)))


def fetch_rows(state, groups: np.ndarray, n_slots: int, n_shards: int
               ) -> dict:
    """The program's final table at the given rows, on the host."""
    import jax.numpy as jnp

    if n_shards > 1:
        idx = (jnp.asarray(groups // n_slots), jnp.asarray(groups % n_slots))
    else:
        idx = (jnp.asarray(groups),)
    out = {"keys": np.asarray(state.keys[idx]),
           "regs": np.asarray(state.regs[idx])}
    if getattr(state, "mit_keys", None) is not None:
        out["mit_keys"] = np.asarray(state.mit_keys[idx])
        out["mit_regs"] = np.asarray(state.mit_regs[idx])
    return out


@dataclasses.dataclass
class Expected:
    """The reference's answer for a sample (or the control's)."""

    verdicts: np.ndarray       # classifier verdicts, before mitigation
    scores: np.ndarray         # class scores
    possible: np.ndarray       # [n, classes] verdicts it may give
    table: dict                # keys / regs of the sampled rows


def expected(sample: Sample, built, control: bool = False) -> Expected:
    dtype = np.float32
    if control:
        import ml_dtypes

        dtype = ml_dtypes.bfloat16
    post, tk, tr, _uniq = reference.replay_registers(
        sample.rows, sample.keys, sample.groups, built.registers, dtype)
    z, z_lo, z_hi = reference.readout(post, built.registers)
    z = z.astype(np.float32)
    s = built.suffix.scores(z, built.params, control=control)
    v = built.suffix.verdicts(s, built.params)
    limits = built.config["check"]["limits"]
    ok = built.suffix.possible(z, z_lo, z_hi, s, built.params, limits)
    return Expected(v.astype(np.int64), s, ok,
                    {"keys": tk, "regs": tr.astype(np.float32)})


def numbers(sample: Sample, ref: Expected, served_verdicts: np.ndarray,
            served_table: dict, built) -> dict:
    """The compared numbers, served output against the reference.

    With mitigation the reference's action table runs on the served
    classifier verdicts wherever the reference could give them (a packet
    one float32 step from a table edge may go either way), so one such
    packet does not throw off the rest of its flow."""
    v = np.asarray(served_verdicts, np.int64)
    n_cls = ref.possible.shape[1]
    is_cls = (v >= 0) & (v < n_cls)
    reachable = np.zeros(len(v), bool)
    reachable[is_cls] = ref.possible[np.flatnonzero(is_cls), v[is_cls]]
    want_v = ref.verdicts
    table = dict(ref.table)
    hits_hi = None
    mit = built.mitigation
    if mit:
        attack = int(mit.get("attack_class", 1))
        given = np.where(reachable, v, ref.verdicts)
        either = ref.possible[:, attack] & (ref.possible.sum(1) > 1)
        want_v, mk, mr, _, hits_hi = reference.replay_mitigation(
            sample.keys, sample.groups, given, mit, np.float32, either)
        table["mit_keys"], table["mit_regs"] = mk, mr

    off = 0
    for k, want in table.items():
        got = np.asarray(served_table[k]).astype(want.dtype)
        if k == "mit_regs":
            h = got[:, 0]
            off += int(np.sum((h < want[:, 0]) | (h > hits_hi)))
            off += int(np.sum(got[:, 1] != want[:, 1]))
        else:
            off += int(np.sum(got != want))
    out = {"table_words_off": off}

    dropped = want_v == reference.MITIGATED
    wrong_drop = dropped != (v == reference.MITIGATED)
    if built.suffix.VERDICT_NUMBER == "verdict_gap":
        g = np.full(len(v), np.inf)
        live = ~dropped & is_cls
        g[live] = built.suffix.gap(ref.scores[live], v[live], built.params)
        g[dropped & ~wrong_drop] = 0.0
        out["verdict_gap"] = float(g.max(initial=0.0))
    else:
        bad = wrong_drop | (~dropped & ~reachable)
        out["verdict_mismatch"] = int(np.sum(bad))
    return out


def control_numbers(sample: Sample, built) -> dict:
    """The control's readings: the lower-precision reference served."""
    import ml_dtypes

    ref = expected(sample, built)
    ctl = expected(sample, built, control=True)
    v, table = ctl.verdicts, dict(ctl.table)
    if built.mitigation:
        v, mk, mr, _, _ = reference.replay_mitigation(
            sample.keys, sample.groups, v, built.mitigation,
            ml_dtypes.bfloat16)
        table["mit_keys"], table["mit_regs"] = mk, mr.astype(np.float32)
    return numbers(sample, ref, v, table, built)


def non_fused(backend_counts: dict) -> int:
    return int(sum(n for k, n in backend_counts.items() if k != FUSED))


def judge(values: dict, limits: dict) -> tuple:
    """-> (correct, {name: {"value", "limit"}}); a number without a limit
    in the configuration fails."""
    checks, ok = {}, True
    for name, value in values.items():
        limit = limits.get(name)
        checks[name] = {"value": value, "limit": limit}
        if limit is None or not value <= limit:
            ok = False
    return ok, checks
