"""The benchmark's own machinery: specs, traffic, traces, costs."""
