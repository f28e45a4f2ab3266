"""The benchmark's plain reference: MOSAIC's numpy oracle, frozen.

``mosaic/`` is a verbatim copy of the pure-numpy modules behind the
program's host oracle (``map_graph`` + ``ChipSim``): the IR, the
architecture and knob grid, the 7 nm calibration table, the compiler
passes (precision, fusion, mapping, scheduling), the cost model, the
simulator and the 20 workload graphs.  It imports nothing of the program
and no JAX, so it runs in plain worker processes beside the one that
holds the chip.  ``oracle.py`` scores a genome on a workload with it.
"""
