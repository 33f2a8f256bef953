"""Traffic mixes: ``<mix>.json`` parameter files, each naming the generator
(``kind``) that reads it — ``open_poisson`` or ``closed_backlog``."""
