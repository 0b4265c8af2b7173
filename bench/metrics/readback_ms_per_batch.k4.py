"""Decider outputs to the host per batch (ms): device wait and transfer
(``broker.decide.readback``)."""

from bench.phases import phase_ms

read = phase_ms("broker.decide.readback")
