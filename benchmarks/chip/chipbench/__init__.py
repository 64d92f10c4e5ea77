"""The chip benchmark's yardstick: traffic generation, work counting,
peaks, trace reduction, the plain reference and the comparison that
decides ``correct``.  Nothing here imports the program under test
except :mod:`chipbench.sut`, which drives it."""
