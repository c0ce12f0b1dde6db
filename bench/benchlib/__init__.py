"""The benchmark's own yardstick: traffic, trace reduction, operation and
byte counts, peaks, the plain reference comparison and the run driver.
Nothing here is imported by the program under test."""
