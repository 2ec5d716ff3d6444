"""The yardstick: device peaks, FLOP counts, statistics, the clock, seeded
weights and data, trace reduction and the result line. Later PRs add files
beside these and edit none of them."""
