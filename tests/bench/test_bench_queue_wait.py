"""The queue-wait reader: the engine's admission stamps of the requests
due in the window, and nothing from an engine that stamps none."""

import types

import pytest

from bench import spec
from bench.loop import Sent

READER = spec.load_module("metrics", "queue_wait_p80_ms")


def _ctx(reqs, w0=10.0, w1=20.0):
    sent = [Sent(i, due, due, req) for i, (due, req) in enumerate(reqs)]
    return types.SimpleNamespace(sent=sent, w0=w0, w1=w1)


def _req(arrival, admit):
    return types.SimpleNamespace(arrival_t=arrival, admit_t=admit)


def test_waits_of_requests_due_in_the_window():
    reqs = [(due, _req(due, due + wait)) for due, wait in
            [(11.0, 0.1), (12.0, 0.2), (13.0, 0.3), (14.0, 0.4)]]
    reqs += [(19.0, _req(19.0, None)),    # no slot by the close: 1.0 so far
             (18.5, _req(18.5, 21.0)),    # a slot after the close: 1.5
             (5.0, _req(5.0, 15.0)),      # due before the window
             (15.0, None)]                # refused by the engine
    # waits 0.1, 0.2, 0.3, 0.4, 1.0, 1.5 s: the p80 lies at rank
    # 0.8 x 5 = 4, the wait so far of the request with no slot
    assert READER.read(_ctx(reqs)) == pytest.approx(1000.0)


def test_nothing_without_admission_stamps():
    old = types.SimpleNamespace(arrival_t=11.0)       # no admit_t field
    assert READER.read(_ctx([(11.0, old)])) is None
    assert READER.read(_ctx([])) is None
