"""utils/stack.py: a thread's frames under one roomy frame, so that no call
site sits on the edge of a CPython frame chunk."""

import threading
import time

from kubernetes_gpu_cluster_tpu.utils.stack import (ROOMY_WORDS, _call_roomy,
                                                    roomy_stack)


def test_roomy_stack_passes_arguments_result_and_name():
    @roomy_stack
    def add(a, b=0, *, c=0):
        """doc"""
        return a + b + c

    assert add(1, 2, c=3) == 6
    assert add.__name__ == "add" and add.__doc__ == "doc"
    assert _call_roomy.__code__.co_stacksize == ROOMY_WORDS


def test_roomy_stack_lets_exceptions_through():
    @roomy_stack
    def boom():
        raise KeyError("x")

    try:
        boom()
    except KeyError as e:
        assert e.args == ("x",)
    else:
        raise AssertionError("no exception")


def _leaf():
    return 0


def _hot(n=20000):
    best = float("inf")
    for _ in range(3):              # the best of three: a busy host
        t = time.perf_counter()
        for _ in range(n):
            _leaf()
        best = min(best, time.perf_counter() - t)
    return best


def _down(k):
    return _down(k - 1) if k else _hot()


def test_no_depth_pays_a_chunk_per_call_under_a_roomy_frame():
    """Without the frame one depth in ~140 pays an mmap and a munmap a call
    (~200x); under it the calls of every depth cost the same."""
    times = []

    def scan():
        times.extend(_down(d) for d in range(300))

    th = threading.Thread(target=roomy_stack(scan))
    th.start()
    th.join()
    assert len(times) == 300
    median = sorted(times)[150]
    assert max(times) < 30 * median, (max(times), median)
