"""The benchmark's yardstick: traffic, window arithmetic, trace reduction,
FLOP counts, peaks and the comparison that decides ``correct``. Nothing in
this package imports the program; ``system.py`` is the one module that does."""
