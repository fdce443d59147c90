"""Plain references of each configuration, importing nothing of the program."""
