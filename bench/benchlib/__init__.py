"""The benchmark's own library: cell loading, traffic, weights, the plain
reference, work counts, trace reduction and the serving window."""
