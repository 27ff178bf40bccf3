"""Traffic kinds that come as files of their own.

A traffic file names its kind under ``"generator"``.
``chipbench.generator.kind(name)`` returns the class that
``generator.KINDS`` holds under ``name``, and otherwise imports
``chipbench/kinds/<name>.py`` (the name matches ``^[a-z0-9_]+$``) and
returns the module's ``KIND``.  So a cell of a new kind comes as new
files: its kind module here, a configuration, a traffic file, its metric
readers and, for a reader of program spans, a recorded trace
``tests/data/<metric>.xplane.pb``; no file of the harness changes.

``KIND`` is a class.  What the harness calls on it:

* ``KIND(config, traffic, seed)``: every input drawn from ``seed`` alone;
* ``trace_setup``: true where the window runs nothing on the device, so
  that a traced run starts before set-up, which does;
* ``setup()``: build the system under test from the configuration, draw
  every request, and warm up each shape the window will use;
* ``request(i) -> int``: drive request ``i`` through the program and
  return the units it completed;
* ``counters() -> dict``: what the program counted over the window, for
  the metric readers (``Context.counters``);
* ``checks()``: compare what the window produced with the plain reference;
  a list of ``(name, value, limit)``, each passing where
  ``value <= limit``.

What the tests and ``control.py`` call on it:

* ``small(config, traffic)`` (static): cut the cell, in place, to a size a
  CPU test run holds, keeping its checks and limits;
* ``faults(traffic) -> {name: fault}`` (static): the faults that can fire
  in a cell of this traffic, each a function of ``mp`` (anything with
  ``mp.setattr(obj, name, value)``) that plants itself in the program;
* ``cpu_control``: whether the CPU test of the control runs the cell;
  where it is true, ``control_checks(dtype)`` gives the control's readings
  on what ``checks()`` read, one per check and in its order, and the
  optional ``control_model()`` puts the control in place before the
  window;
* ``fresh_jit`` (optional, false where absent): its faults patch code that
  runs traced, so a test clears JAX's caches around each run.
"""
