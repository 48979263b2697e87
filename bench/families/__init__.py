"""Configuration families: data and weights from the seed, and the program's
problem object built from them.  A configuration file names its family."""
