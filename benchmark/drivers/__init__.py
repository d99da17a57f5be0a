"""One module a driver: the code that puts the program under a traffic
mix.  A traffic file names its driver; a driver is a class ``Driver``
with ``setup()``, ``window(seconds)``, ``check(checks)`` and ``close()``.
Only this package imports the program."""
