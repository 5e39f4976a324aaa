"""The chip benchmark's yardstick: traffic, weights, reference, trace reduction."""
