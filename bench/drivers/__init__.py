"""One driver per kind of traffic; a traffic file's ``kind`` names it.

Each module defines ``Driver(cfg, traffic, seed, devices)`` with
``setup()``, ``window(seconds) -> harness.Window``, ``release()``,
``outputs()`` (what the timed path produced), ``control_outputs()``
(the reference in the program's place, a precision step lower) and
``compare(outputs) -> {name: number}``; ``check()`` compares
``outputs()``.
"""
