"""The benchmark's own library: everything the yardstick needs that is not
the system under test.  Nothing here imports ``repro`` at module level, so
the CPU tests can load the pieces that need no engine."""
