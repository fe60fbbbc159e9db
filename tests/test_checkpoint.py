"""core/checkpoint.py — the fault-tolerance substrate.

The happy paths (roundtrip, async+gc, mismatch raises), and the corners the
simulation checkpointing layer (core/simcheck.py) leans on: the GC
keep-window under interleaved sync/async saves, crash debris (a stale
``step_N.tmp`` dir from a SIGKILLed write) never corrupting later saves or
discovery, the structure-mismatch message naming the offending keys, and
manifest ``extras`` round-tripping.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import checkpoint


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.asarray([1, 2], jnp.int32),
                  "d": jnp.asarray(3.5, jnp.bfloat16)}}
    checkpoint.save(str(tmp_path), 7, tree)
    assert checkpoint.latest_step(str(tmp_path)) == 7
    like = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
    out = checkpoint.restore(str(tmp_path), 7, like)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_async_and_gc(tmp_path):
    ck = checkpoint.AsyncCheckpointer(str(tmp_path), keep=2)
    tree = {"w": jnp.ones((4,))}
    for s in (1, 2, 3, 4):
        ck.save_async(s, tree)
    ck.wait()
    steps = checkpoint.list_steps(str(tmp_path))
    assert steps == [3, 4]              # older checkpoints gc'd
    assert checkpoint.latest_step(str(tmp_path)) == 4


def test_checkpoint_structure_mismatch_raises(tmp_path):
    checkpoint.save(str(tmp_path), 1, {"a": jnp.ones(3)})
    with pytest.raises(ValueError):
        checkpoint.restore(str(tmp_path), 1, {"zzz": jnp.ones(3)})



def test_gc_keep_window_exact(tmp_path):
    ck = checkpoint.AsyncCheckpointer(str(tmp_path), keep=3)
    tree = {"w": jnp.arange(4, dtype=jnp.float32)}
    for s in range(1, 9):
        ck.save_async(s, tree)
    ck.wait()
    assert checkpoint.list_steps(str(tmp_path)) == [6, 7, 8]
    # every survivor is restorable, not just listed
    for s in (6, 7, 8):
        out = checkpoint.restore(str(tmp_path), s, {"w": jnp.zeros(4)})
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.arange(4, dtype=np.float32))


def test_stale_tmp_dir_is_harmless_and_collected(tmp_path):
    """A crash mid-write leaves ``step_N.tmp`` — it must not shadow real
    checkpoints, must not break discovery, and the GC must sweep it."""
    d = str(tmp_path)
    checkpoint.save(d, 1, {"a": jnp.ones(2)})
    # simulate a killed writer: partial tmp dir with a half-written file
    stale = os.path.join(d, "step_000000002.tmp")
    os.makedirs(stale)
    with open(os.path.join(stale, "arrays.npz"), "w") as f:
        f.write("partial garbage")
    assert checkpoint.list_steps(d) == [1]          # tmp is invisible
    assert checkpoint.latest_step(d) == 1
    # a later save of the SAME step must overwrite the debris atomically
    checkpoint.save(d, 2, {"a": jnp.full(2, 5.0)})
    assert checkpoint.latest_step(d) == 2
    out = checkpoint.restore(d, 2, {"a": jnp.zeros(2)})
    np.testing.assert_array_equal(np.asarray(out["a"]), np.full(2, 5.0))
    ck = checkpoint.AsyncCheckpointer(d, keep=2)
    ck.save_async(3, {"a": jnp.ones(2)})
    ck.wait()
    leftovers = [n for n in os.listdir(d) if n.endswith(".tmp")]
    assert leftovers == [], f"gc left crash debris: {leftovers}"


def test_latest_step_survives_crash_before_latest_update(tmp_path):
    """Dying between the atomic rename and the LATEST write must not roll
    the run back a save: the directory listing is authoritative."""
    d = str(tmp_path)
    checkpoint.save(d, 4, {"a": jnp.ones(2)})
    checkpoint.save(d, 9, {"a": jnp.ones(2)})
    with open(os.path.join(d, "LATEST"), "w") as f:
        f.write("4")                                # stale pointer
    assert checkpoint.latest_step(d) == 9


def test_structure_mismatch_message_names_keys(tmp_path):
    d = str(tmp_path)
    checkpoint.save(d, 1, {"present": jnp.ones(3), "both": jnp.ones(1)})
    with pytest.raises(ValueError, match="structure mismatch") as e:
        checkpoint.restore(d, 1, {"wanted": jnp.ones(3), "both": jnp.ones(1)})
    msg = str(e.value)
    assert "wanted" in msg and "present" in msg, \
        f"mismatch message must name missing AND extra keys: {msg}"
    assert "'both'" not in msg, f"matching keys are not mismatches: {msg}"


def test_restore_shape_mismatch_names_key(tmp_path):
    d = str(tmp_path)
    checkpoint.save(d, 1, {"a": jnp.ones((2, 3))})
    with pytest.raises(ValueError, match="a"):
        checkpoint.restore(d, 1, {"a": jnp.ones((3, 2))})


def test_manifest_extras_roundtrip(tmp_path):
    d = str(tmp_path)
    extras = {"kind": "engine", "knobs": {"capacity": 128, "dt": 0.25}}
    checkpoint.save(d, 3, {"a": jnp.ones(2)}, extras=extras)
    man = checkpoint.load_manifest(d, 3)
    assert man["step"] == 3
    assert man["extras"] == json.loads(json.dumps(extras))
    # async path threads extras through too
    ck = checkpoint.AsyncCheckpointer(d, keep=2)
    ck.save_async(4, {"a": jnp.ones(2)}, extras={"kind": "dist"})
    ck.wait()
    assert checkpoint.load_manifest(d, 4)["extras"] == {"kind": "dist"}
