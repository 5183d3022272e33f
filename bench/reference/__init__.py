"""Plain references the benchmark judges served plans against.  They import
nothing of the program under test."""
