"""Plain references, one module per kind of model; a configuration's
file names its module under `"reference"`."""
