"""The benchmark's own code: cell specs, traffic, window accounting,
trace reduction, roofline arithmetic and the comparison that decides
``correct``.  Nothing here is imported by the program under test."""
