"""The chip benchmark's own code: traffic generation, the trace reduction,
the plain references and the checks.  It imports the program only to drive
the system under test (see ``generator.py``)."""
