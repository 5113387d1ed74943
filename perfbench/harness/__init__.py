"""The chip benchmark's own code: the yardstick that later changes to the
program are measured with. Nothing here is imported by the program."""
