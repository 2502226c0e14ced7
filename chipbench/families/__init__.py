"""Model families: how a configuration file becomes the program's graph,
its seeded weights and its fed batches, and the plain float32 reference
that ``correct`` is judged against. A configuration names its family."""
