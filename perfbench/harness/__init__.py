"""The chip benchmark's own code: specs, device check, work counts, the
plain reference, traffic drivers, trace reduction and the comparison that
decides ``correct``.  Nothing here is imported by the program."""
