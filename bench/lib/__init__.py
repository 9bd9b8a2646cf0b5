"""The benchmark's own code: corpus and traffic generation, the exact
reference, the comparison that decides ``correct``, the trace reduction and
the roofline work functions. It imports nothing of the program except in
``serve.py``, which drives the system under test."""
