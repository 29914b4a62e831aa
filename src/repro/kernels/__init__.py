"""Pallas kernels for the splitAtt hot-spot and forest inference.  Callers
go through :mod:`repro.kernels.ops`, which compiles them with Mosaic on a
TPU and interprets them on the ``cpu`` backend only;
:mod:`repro.kernels.autotune` plans the block sizes and VMEM, and
:mod:`repro.kernels.compaction` keeps deep-superstep traffic proportional to
live cases.  :mod:`repro.kernels.ref` holds the pure-jnp oracles the tests
compare against.
"""
