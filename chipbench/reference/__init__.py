"""Plain float32 references, one module per ``model_type`` of a
configuration file. They import nothing of the program."""
