"""The timed path broken underneath, and ``correct`` comes out false.

Skips the harness's look for a chip (``--rehearse``) and drives the rest
of a run in this process, once for each fault a broker cell can have:
half of a batch left out, and an answer altered where it is produced.
(A step that returns its state unchanged and an exchange between chips
left out are faults of training and of several chips: no cell here has
either.)"""

import json

import pytest

import run as harness


def half_of_the_batch_left_out(srv):
    from mqtt_tpu.topics import Subscribers

    inner = srv.matcher.match_topics_async

    def broken(topics, *a, **kw):
        resolve = inner(topics, *a, **kw)

        def half():
            out = resolve()
            return [r if i % 2 else Subscribers() for i, r in enumerate(out)]

        return half

    srv.matcher.match_topics_async = broken


def an_answer_altered_where_it_is_produced(srv):
    inner = srv._fan_out
    calls = [0]

    def broken(pk, subscribers, *a, **kw):
        calls[0] += 1
        if calls[0] % 50 == 0:
            body = bytearray(pk.payload)
            body[11] ^= 1  # the sequence number's last byte
            pk.payload = bytes(body)
        return inner(pk, subscribers, *a, **kw)

    srv._fan_out = broken


@pytest.mark.parametrize(
    "fault", [half_of_the_batch_left_out, an_answer_altered_where_it_is_produced]
)
@pytest.mark.parametrize("cell", ["telemetry-1m.saturate", "stresser-100.challenge"])
def test_fault_reads_not_correct(cell, fault, capsys):
    if cell.startswith("stresser") and fault is half_of_the_batch_left_out:
        pytest.skip("an echo client waits for every message: this fault stalls "
                    "the traffic before the comparison can see it")
    rc = harness.main(
        ["--workload", cell, "--seed", "77", "--seconds", "2", "--trace", "0",
         "--rehearse"],
        sabotage=fault,
    )
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["compared"].values())
