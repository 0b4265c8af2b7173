"""The MESI kernel's share of its HBM roofline (%)."""

from bench.readers import roofline

read = roofline("mesi_tick")
