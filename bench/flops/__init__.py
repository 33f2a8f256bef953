"""Required FLOPs per token, one module per model family (``family`` in the
configuration file): what the algorithm needs, not what a program
executes."""
