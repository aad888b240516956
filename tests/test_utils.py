import pytest
import logging

import numpy as np

from ml_recipe_tpu.utils import RngPool, get_logger, set_seed, time_profiler
from ml_recipe_tpu.utils.profiler import StepTimer

# no-jit / tiny-jit module: part of the <2 min unit tier (VERDICT r2 #7)
pytestmark = pytest.mark.unit


def test_get_logger_resets_handlers(tmp_path):
    log_file = tmp_path / "run.log"
    logger = get_logger(filename=str(log_file), logger_name="t1")
    logger.info("hello")
    # second call must not duplicate handlers
    get_logger(logger_name="t2")
    assert len(logging.root.handlers) == 1
    assert "hello" in log_file.read_text()


def test_set_seed_determinism():
    set_seed(123)
    a = np.random.rand(4)
    set_seed(123)
    b = np.random.rand(4)
    np.testing.assert_array_equal(a, b)
    assert set_seed(None) is None


def test_rng_pool_keys_distinct_and_stable():
    import jax

    pool = RngPool(7)
    k1 = pool.key("dropout", step=0)
    k2 = pool.key("dropout", step=1)
    k3 = pool.key("bpe", step=0)
    d1 = jax.random.key_data(k1)
    assert not np.array_equal(d1, jax.random.key_data(k2))
    assert not np.array_equal(d1, jax.random.key_data(k3))

    pool2 = RngPool(7)
    np.testing.assert_array_equal(d1, jax.random.key_data(pool2.key("dropout", step=0)))


def test_rng_pool_host_rng():
    pool = RngPool(7)
    a = pool.host_rng("sample", 3).random(5)
    b = RngPool(7).host_rng("sample", 3).random(5)
    np.testing.assert_array_equal(a, b)


def test_time_profiler_passthrough():
    @time_profiler
    def add(a, b):
        return a + b

    assert add(2, 3) == 5


def test_step_timer():
    t = StepTimer(warmup=1)
    for _ in range(3):
        t.start()
        t.stop()
    assert t.count == 3
    assert t.mean() >= 0.0


def test_lagged_consumer_orders_and_flushes():
    from ml_recipe_tpu.utils.pipeline import LaggedConsumer

    seen = []
    lag = LaggedConsumer(lambda *a: seen.append(a))
    lag.feed(1, "a")
    assert seen == []          # first feed: nothing consumed yet
    lag.feed(2, "b")
    assert seen == [(1, "a")]  # one-step lag
    lag.flush()
    assert seen == [(1, "a"), (2, "b")]
    lag.flush()                # idempotent
    assert seen == [(1, "a"), (2, "b")]
    lag.feed(3, "c")
    lag.flush()
    assert seen[-1] == (3, "c")


def test_lagged_consumer_total_autoflushes():
    from ml_recipe_tpu.utils.pipeline import LaggedConsumer

    seen = []
    lag = LaggedConsumer(lambda x: seen.append(x), total=3)
    lag.feed(1); lag.feed(2)
    assert seen == [1]
    lag.feed(3)            # final feed: consumes 2 AND 3 (auto-flush)
    assert seen == [1, 2, 3]
    lag.flush()            # still idempotent afterwards
    assert seen == [1, 2, 3]


def test_lagged_consumer_grouped_mode():
    """group > 1: the oldest `group` feeds arrive in ONE consume([...])
    call once `depth` newer items are in flight; flush delivers the tail
    (possibly short); group=1 keeps the unpacked-args convention."""
    from ml_recipe_tpu.utils.pipeline import LaggedConsumer

    calls = []
    lag = LaggedConsumer(lambda batch: calls.append(batch), depth=2, group=3)
    for i in range(8):
        lag.feed(i, f"item{i}")
    # a full group is delivered each time group+depth feeds are pending,
    # always keeping `depth` newest items in flight
    assert calls == [
        [(0, "item0"), (1, "item1"), (2, "item2")],
        [(3, "item3"), (4, "item4"), (5, "item5")],
    ]
    lag.flush()
    assert calls[2] == [(6, "item6"), (7, "item7")]  # short tail group
    lag.flush()  # idempotent
    assert len(calls) == 3

    # group=1 unchanged: unpacked args, one-late delivery
    single = []
    lag1 = LaggedConsumer(lambda a, b: single.append((a, b)), depth=1)
    lag1.feed(1, "a"); lag1.feed(2, "b")
    assert single == [(1, "a")]
    lag1.flush()
    assert single == [(1, "a"), (2, "b")]
