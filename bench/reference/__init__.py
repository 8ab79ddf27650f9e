"""The benchmark's plain reference: the scalar CarbonPATH model.

The modules beside this file are a copy of the program's scalar model
(``src/repro/core`` at the commit that added the benchmark) with the
imports made relative, so the comparison that decides ``correct`` imports
nothing of the program it judges. ``decode`` reads the program's encoded
design rows (the answers being checked) with its own column layout.
"""
