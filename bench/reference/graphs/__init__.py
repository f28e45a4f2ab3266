"""Workload graphs that a configuration brings to the reference.

``oracle.py`` scores the 20 suite names with the frozen suite graphs.
Any other workload name ``<name>`` is looked up here: ``<name>.py``
defines ``build()``, which returns a ``WorkloadGraph`` of the frozen IR
(``..mosaic.ir``).  The reference runs it through the same precision
assignment and fusion as a suite graph.

- The file imports the frozen builders relatively, for example
  ``from ..mosaic.workloads.transformer import attention_block``, and
  nothing of the program and no JAX: the reference has to stay
  independent of what it checks.
- Its name is not a suite name: a file that reuses one is an error.
- A name that is neither a suite name nor a file here is an error that
  names both places.

A configuration that brings a graph brings two independent builders of
the same published configuration: the program's, which the timed path
runs, and this one.  The comparison that decides ``correct`` holds the
two against each other pair by pair (``off_share``), so a builder that
departs from the published widths shows there.
"""
