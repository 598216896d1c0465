"""The benchmark harness: cells, traffic, the run's window, traces, the
plain reference and the check that decides ``correct``.

Nothing here is imported by the program under ``src/``; the harness drives
the program's public serving surface (``ServingEngine`` and
``RequestScheduler``) and reads its counters, and keeps every piece of
arithmetic that turns a run into numbers under ``bench/``.
"""
