"""General runners, one per kind of traffic. A traffic file names its
runner (``"runner": "<module>"``). The module defines the class
``Run(config, traffic, seed)``, with ``setup()``, ``measure(window,
seconds)``, ``summary(window)``, ``release()`` and ``check()``, and the
function ``control(config, seed)`` that ``benchmark/control.py`` calls.
"""
