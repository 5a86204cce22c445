"""The benchmark's own library: everything the yardstick is made of lives
under ``bench/`` (traffic generation, trace reduction, peaks, operation and
byte counts, plain references, the comparison that decides ``correct``).
From the program it takes only the system under test."""
