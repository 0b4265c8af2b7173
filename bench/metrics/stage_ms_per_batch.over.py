"""Host staging per batch (ms): the broker's batch arrays, dirty-chunk
masks and directory copies (``broker.stage``) and the decider's host to
device copies (``broker.decide.stage``)."""

from bench.phases import STAGE, phase_ms

read = phase_ms(*STAGE)
