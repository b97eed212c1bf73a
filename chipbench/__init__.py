"""The benchmark: three training cells on the v5e, driven by data.

Everything a later PR may not move lives here: data generation, the
window, the plain reference and the comparison that decides
``correct``, the work counts, the peaks and the trace reduction.  See
`PERF.md` ("How to add a cell / a metric") for the lookup by name.
"""
