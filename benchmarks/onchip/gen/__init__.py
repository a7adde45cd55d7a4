"""Traffic generation: copies of the program's generators, and the one
general generator that reads a traffic file."""
