"""The chip benchmark of the dataframe engine: ``bench/run.py`` runs one
cell of ``BENCHMARK.json`` (a configuration under a traffic mix) once."""
