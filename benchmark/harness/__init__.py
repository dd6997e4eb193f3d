"""The yardstick: manifest, start-up, client, statistics, trace reduction, costs."""
