"""From the profiler's trace and the program's span file to numbers."""
