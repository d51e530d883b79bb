"""The yardstick: traffic, population, plain reference, trace reduction."""
