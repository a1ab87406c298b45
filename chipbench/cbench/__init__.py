"""Yardstick of the chip benchmark: everything that turns a run into numbers.

Traffic generation, the serving loop, the trace reduction, the FLOP and byte
counts, the table of peaks, the seeded weights and the float32 reference
that decides ``correct``. It imports nothing of the program under test
except its public serving API (``LM``, ``ServeSession``, ``ArcaneEngine``,
``ModelConfig``), and the reference imports nothing of it at all.
"""
