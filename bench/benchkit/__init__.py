"""The benchmark's own code: cell lookup, traffic generators, meters, the trace
reduction and the references that decide ``correct``. Nothing here is
imported by the program under test."""
