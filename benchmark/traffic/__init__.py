"""Traffic: one general generator per kind of corpus, and one data file of
parameters per mix (``<traffic>.json``, which names its generator)."""
