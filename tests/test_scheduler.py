"""FixedShapeScheduler: FIFO admission into padding-bucketed cohorts, the
core the profiler service admits reads through."""

import pytest

from repro.serve.scheduler import FixedShapeScheduler, pow2_buckets


def test_scheduler_fifo_cohorts_and_slot_cap():
    s = FixedShapeScheduler(slots=3, buckets=(12, 15, 16))
    for i in range(7):
        s.submit(f"item{i}", size=10 + i)
    cohorts = s.drain()
    assert [list(c.items) for c in cohorts] == [
        ["item0", "item1", "item2"], ["item3", "item4", "item5"], ["item6"]]
    # each cohort pads to the bucket of its largest item
    assert [c.length for c in cohorts] == [12, 15, 16]
    assert s.next_cohort() is None and len(s) == 0


def test_scheduler_buckets_bound_the_shape_set():
    s = FixedShapeScheduler(slots=4, buckets=(64, 128, 256))
    for size in (10, 60, 64, 65):
        s.submit(size, size=size)
    (c,) = s.drain()
    assert c.length == 128                   # bucket of the largest item
    assert s.bucket_for(1) == 64 and s.bucket_for(256) == 256
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        s.submit("too-big", size=300)


def test_scheduler_validation():
    with pytest.raises(ValueError):
        FixedShapeScheduler(slots=0, buckets=(16,))
    with pytest.raises(ValueError):
        FixedShapeScheduler(slots=1, buckets=())
    s = FixedShapeScheduler(slots=1, buckets=(1, 16))
    with pytest.raises(ValueError):
        s.submit("x", size=-1)
    s.submit("x", size=0)                    # zero-size items are admitted
    assert s.next_cohort().length == 1


def test_pow2_buckets():
    assert pow2_buckets(16, 150) == (16, 32, 64, 128, 256)
    assert pow2_buckets(100, 100) == (128,)
    with pytest.raises(ValueError):
        pow2_buckets(0, 10)


@pytest.mark.parametrize("size,bucket", [
    (1, 16), (16, 16), (17, 32), (256, 256), (257, 512), (4096, 4096),
    (4097, None)])
def test_bucket_for_boundaries_of_the_service_default(size, bucket):
    """The service's default buckets: a read goes to the smallest bucket
    that holds it, a bucket's own length included; past 4,096 it is
    refused."""
    s = FixedShapeScheduler(slots=1, buckets=pow2_buckets(16, 4096))
    if bucket is None:
        with pytest.raises(ValueError, match="exceeds the largest bucket"):
            s.bucket_for(size)
    else:
        assert s.bucket_for(size) == bucket
