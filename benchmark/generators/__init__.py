"""Traffic generators: one module per kind of mix. A traffic file
(`benchmark/traffic/<name>.json`) names its generator under "generator"; a
later PR adds a mix as a data file, and a module here only for a new kind."""
