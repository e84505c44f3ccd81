"""Drivers: the only files of the benchmark that import the program.

A driver is a module with a class ``Run(cell, config, *, seed, seconds,
devices, note)`` offering ``setup()``, ``trace_slice()``, ``window()`` and
``check(obs)``; a cell's file names its driver."""
