"""The benchmark's own code: traffic, weights, reference, comparison,
trace reduction, operation counts and the run loop. Nothing here is
imported by the program under test."""
