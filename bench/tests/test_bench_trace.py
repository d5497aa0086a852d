"""The trace reduction, on a trace recorded on the CPU by jax.profiler."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import trace
from bench.tests.conftest import cpu_line


def test_self_times_charge_nested_events_to_themselves():
    # (start, duration, name): two fusions inside a while loop
    evs = [(0, 10, "while"), (2, 3, "fusion"), (6, 2, "fusion")]
    assert trace._self_times(evs, 0, 10) == {"while": 5, "fusion": 5}
    # clipped to the window [4, 10]
    assert trace._self_times(evs, 4, 10) == {"while": 3, "fusion": 3}


def test_busy_is_the_union_and_holes_are_the_rest():
    busy, at, length = trace._busy_and_gaps(
        np.array([5.0, 0.0, 2.0]), np.array([7.0, 3.0, 4.0]), 0.0, 10.0)
    assert busy == 6.0
    assert at.tolist() == [4.0, 7.0] and length.tolist() == [1.0, 3.0]


def test_short_names():
    assert trace.short_name(
        "%fusion.34 = f32[490752,16]{0,1:T(8,128)S(1)} fusion(f32[8]"
        " %x), kind=kCustom") == "fusion.34 f32[490752,16]"
    assert trace.short_name(
        "%while.34 = (s32[]{:T(128)}, f32[9]) while(%t)") == "while.34 tuple"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("window"):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("submit"):
            for _ in range(3):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("wave_wait"):
            time.sleep(0.2)
        f(x).block_until_ready()
        wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    return trace.reduce(trace.find_xplane(d), device_line=cpu_line), wall


def test_busy_window_and_named_gap(recorded):
    s, wall = recorded
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s <= wall + 0.05
    # the sleep is the longest hole in the device's timeline
    name, seconds = s.idle_gaps[0]
    assert name == "wave_wait" and 0.19 < seconds < 0.3
    assert len(s.idle_gaps) <= trace.TOP and len(s.device_ops) <= trace.TOP
    assert s.device_ops and all(t >= 0 for _, t in s.device_ops)


def test_no_window_span_is_an_error(tmp_path):
    d = str(tmp_path)
    jax.profiler.start_trace(d)
    jnp.ones(3).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError):
        trace.reduce(trace.find_xplane(d), device_line=cpu_line)
