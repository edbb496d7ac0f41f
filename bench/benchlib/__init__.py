"""Shared code of the on-chip benchmark: declarations, corpus, reference,
traffic, trace reduction, roofline arithmetic and the correctness check.

Everything here is the yardstick.  It imports nothing of the program
under test except where it drives it (``cell.py``), so a change to the
program cannot change how it is measured.
"""
