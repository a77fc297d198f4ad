"""Plain references of the model families the benchmark serves, one module
per family, named by a configuration's ``family``."""
