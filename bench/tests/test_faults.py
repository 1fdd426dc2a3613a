"""A whole run, all but the look for a chip, with the timed path broken
underneath: ``correct`` has to come out false for each fault a cell can
have, and true for the path as it is."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec
from bench.tests.helpers import run_tiny, tiny_cell

CELLS = [("tiny-flow-ddos-mlp", "tiny-churn"),
         ("tiny-mitigate-mat", "tiny-flood16")]


def _stale_state(orig):
    def dispatch(self, state, X, valid=None):
        _new, v = orig(self, state, X, valid)
        return state, v
    return dispatch


def _half_batch(orig):
    def dispatch(self, state, X, valid=None):
        if valid is not None:
            valid = np.array(valid)
            valid[len(valid) // 2:] = 0
        return orig(self, state, X, valid)
    return dispatch


def _altered_answer(orig):
    def dispatch(self, state, X, valid=None):
        state, v = orig(self, state, X, valid)
        return state, v.at[::8].set(jnp.where(v[::8] == 1, 0, 1))
    return dispatch


def _compile_in_window(orig):
    def dispatch(self, state, X, valid=None):
        jax.jit(lambda x: x + 1)(jnp.zeros(3)).block_until_ready()
        return orig(self, state, X, valid)
    return dispatch


FAULTS = {"stale_state": _stale_state, "half_batch": _half_batch,
          "altered_answer": _altered_answer,
          "compile_in_window": _compile_in_window}


@pytest.mark.parametrize("config,traffic", CELLS)
def test_sound_path_is_correct(config, traffic):
    res = run_tiny(tiny_cell(config, traffic))
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("config,traffic", CELLS)
def test_fault_is_caught(monkeypatch, config, traffic, fault):
    from repro.flowstate.pipeline import StatefulPipeline

    monkeypatch.setattr(StatefulPipeline, "dispatch",
                        FAULTS[fault](StatefulPipeline.dispatch))
    res = run_tiny(tiny_cell(config, traffic))
    assert not res["correct"], res["checks"]


SHARDED = """
import json, sys, time
sys.path[:0] = [{src!r}, {root!r}]
import numpy as np
from bench.tests.helpers import run_tiny, tiny_cell
cell = tiny_cell("tiny-flow-ddos-mlp-x4", "tiny-churn", chips=4)
out = {{"sound": run_tiny(cell)["correct"]}}
import repro.serve.sharded as sharded
sharded.shard_of_key = lambda keys, n: np.zeros(len(keys), np.int64)
out["no_exchange"] = run_tiny(cell)["correct"]
print(json.dumps(out))
"""


def test_sharded_exchange_left_out_is_caught():
    """Four host devices in a child process: every packet routed to the
    first chip's table (the exchange between chips left out)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SHARDED.format(src=str(spec.ROOT / "src"), root=str(spec.ROOT))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = __import__("json").loads(p.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "no_exchange": False}
