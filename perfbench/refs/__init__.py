"""Plain references of the configurations' models. They import nothing
of the program under test."""
