"""Plain references: the same query semantics, written from scratch in
numpy over the generated tables.  Nothing here imports the program or
takes anything it made; the tables are read from the files the benchmark
wrote.  One module per query kind, found by the kind's name, each with
``expected``, ``control`` (the reference one precision step below the
configuration's) and ``compare``."""
