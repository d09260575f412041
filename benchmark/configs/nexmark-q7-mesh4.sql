-- NEXmark Query 7, highest bid: the bids that carry the highest price of
-- each 10 s tumbling window, as per-auction max joined to the window's
-- global max (the shape of tests/smoke/queries/nexmark_q7.sql), read from
-- the nexmark connector. The dollar names are filled from the configuration's
-- generator settings, the traffic mix's rate and --seed.
CREATE TABLE nexmark (
  "bid" BOOLEAN, "bid.auction" BIGINT, "bid.price" BIGINT
) WITH (
  connector = 'nexmark',
  inter_event_micros = $inter_event_micros,
  first_event_micros = $first_event_micros,
  event_rate = $event_rate,
  seed = $seed
);
CREATE TABLE highest_bids (
  auction BIGINT, price BIGINT, ws TIMESTAMP
) WITH (connector = '$sink', type = 'sink');
INSERT INTO highest_bids
SELECT PerAuction.auction, PerAuction.mx, PerAuction.window.start
FROM (
  SELECT "bid.auction" AS auction, max("bid.price") AS mx,
    tumble(interval '10 seconds') AS window
  FROM nexmark WHERE "bid" GROUP BY "bid.auction", window
) AS PerAuction
JOIN (
  SELECT max("bid.price") AS mx,
    tumble(interval '10 seconds') AS window
  FROM nexmark WHERE "bid" GROUP BY window
) AS GlobalMax
ON PerAuction.window = GlobalMax.window AND PerAuction.mx = GlobalMax.mx;
