"""ZeRO-1 optimizer-state sharding (FFConfig.zero_optimizer).

SURVEY §2.3 lists ZeRO-style optimizer sharding as design headroom over
the reference.  Contracts: state of replicated params shards over the
free mesh axes (~1/N per device), training numerics are unchanged, and
non-divisible leaves are skipped, not broken.
"""

import numpy as np
import pytest

import flexflow_tpu as ff


def _train(zero, steps=4, opt="adam"):
    cfg = ff.FFConfig(batch_size=16, zero_optimizer=zero)
    m = ff.FFModel(cfg)
    inp = m.create_tensor((16, 8), nchw=False)
    t = m.dense(inp, 64, activation="relu", name="fc1")
    t = m.dense(t, 10, name="fc2")   # out dim 10: bias not divisible by 8
    t = m.softmax(t, name="sm")
    optimizer = (ff.AdamOptimizer(alpha=0.01) if opt == "adam"
                 else ff.SGDOptimizer(lr=0.1, momentum=0.9))
    m.compile(optimizer, "sparse_categorical_crossentropy", ["accuracy"])
    m.init_layers(seed=12)
    rng = np.random.default_rng(7)
    x = rng.standard_normal((16, 8), dtype=np.float32)
    y = rng.integers(0, 10, size=(16, 1), dtype=np.int32)
    dl = ff.DataLoader(m, {inp: x}, y)
    for _ in range(steps):
        dl.next_batch(m)
        m.train_iteration()
    m.sync()
    return m


@pytest.mark.parametrize("opt", ["adam", "sgd"])
def test_zero_numerics_match_plain(devices, opt):
    ref = _train(False, opt=opt)
    z = _train(True, opt=opt)
    for name in ("fc1", "fc2"):
        np.testing.assert_allclose(ref.get_parameter(name, "kernel"),
                                   z.get_parameter(name, "kernel"),
                                   rtol=1e-5, atol=1e-6)


def test_zero_state_actually_sharded(devices):
    m = _train(True, steps=1)
    state = m._opt_state["m"]
    # fc1 kernel (8, 64): dim0 divisible by the 8 free axes -> sharded
    arr = state["fc1"]["kernel"]
    assert arr.sharding.spec and arr.sharding.spec[0] is not None
    per_dev = max(int(np.prod(s.data.shape))
                  for s in arr.addressable_shards)
    assert per_dev == arr.size // 8
    # fc2 bias (10,): 10 % 8 != 0 -> skipped, stays replicated
    b = state["fc2"]["bias"]
    assert all(e is None for e in b.sharding.spec)
    # plain run keeps everything replicated
    ref = _train(False, steps=1)
    rarr = ref._opt_state["m"]["fc1"]["kernel"]
    assert all(e is None for e in rarr.sharding.spec)


def test_zero_state_stays_sharded_across_steps(devices):
    """The computed state re-enters the step still sharded (the
    with_sharding_constraint in apply holds between iterations)."""
    m = _train(True, steps=3)
    arr = m._opt_state["m"]["fc1"]["kernel"]
    assert arr.sharding.spec and arr.sharding.spec[0] is not None


@pytest.mark.parametrize("opt", ["adam", "sgd"])
def test_zero_params_stay_replicated_and_the_step_is_one_program(devices,
                                                                 opt):
    """The new parameters are pinned to their own specs: left to the
    partitioner they came back sharded as the state they were computed
    from, and the second step was traced and compiled anew for them."""
    m = _train(True, steps=3, opt=opt)
    for name in ("fc1", "fc2"):
        for arr in m._params[name].values():
            assert all(e is None for e in arr.sharding.spec), arr.sharding
    assert m._train_step_fn._cache_size() == 1


def test_zero_state_checkpoint_roundtrip(tmp_path, devices):
    """Sharded optimizer state survives save/load: values match AND the
    loaded state carries the ZeRO layout again (not silently
    replicated)."""
    m = _train(True, steps=2)
    before = np.asarray(m._opt_state["m"]["fc1"]["kernel"])
    path = str(tmp_path / "ck.npz")
    m.save(path)
    m2 = _train(True, steps=1)
    m2.load(path)
    arr = m2._opt_state["m"]["fc1"]["kernel"]
    np.testing.assert_allclose(np.asarray(arr), before,
                               rtol=1e-6, atol=1e-7)
    assert arr.sharding.spec and arr.sharding.spec[0] is not None, \
        arr.sharding
    np.testing.assert_allclose(m2.get_parameter("fc1", "kernel"),
                               m.get_parameter("fc1", "kernel"),
                               rtol=1e-6, atol=1e-7)
