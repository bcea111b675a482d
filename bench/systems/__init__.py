"""Adapters of the systems under test, one module per deployment kind.

A configuration file names its adapter under ``"system"``.  Each adapter has
a ``System(cfg, mix, seed, traced)`` whose constructor is the set-up (data
on the device, every shape of the mix warmed up), ``window(seconds, spans,
run)`` that drives the measured window and fills ``run``, and ``check()``
that compares what the window produced with ``bench/reference.py`` and
returns ``{name: (value, limit)}``.
"""
