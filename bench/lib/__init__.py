"""The benchmark's own code: traffic, weights, comparison, trace
reduction, operation counts and the run loop, for any network; what
belongs to one network is its adapter's (``bench/arch/<arch>.py``) and
its reference's (``bench/reference``). Nothing here is imported by the
program under test."""
